"""Cluster launcher: run a training program inside a joined multi-host
cluster — `python -m hivemall_tpu.runtime.launch [cluster flags] prog.py
[prog args...]`.

The reference deploys its distributed tier as daemon processes fanned out
over ssh (`java -jar hivemall-mixserv-*-fat.jar`, ref: bin/mixserv_daemon.sh
start branch; fleet control ref: bin/mixserv_cluster.sh:44-56). TPU-native
there is no separate server binary to start: the "fleet" is N identical SPMD
jax processes, so the launcher's job is (1) join the JAX coordination
service (runtime/cluster.py::init_cluster — the coordinator replaces
conf/MIXSERV_LIST's server fleet), then (2) hand the process over to the
user's unmodified training program via runpy. The same script scales from
one process to N hosts with zero code changes; collectives ride ICI within
a host and DCN across hosts.

Cluster flags come either from the CLI (--coordinator/--num-procs/--proc-id)
or from HIVEMALL_TPU_COORDINATOR / _NUM_PROCS / _PROC_ID (set per-host by
bin/hivemall_tpu_daemon.sh). A `-mix host1,host2` style list (the
reference's client option, ref: LearnerBaseUDTF.java:98) is accepted via
--mix and maps its first host to the coordinator.
"""

from __future__ import annotations

import argparse
import os
import runpy
import sys

from hivemall_tpu.runtime.cluster import init_cluster, parse_mix_option


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="python -m hivemall_tpu.runtime.launch",
        description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--coordinator", default=None,
                    help="host:port of process 0 (default: env/single-process)")
    ap.add_argument("--mix", default=None,
                    help="reference-style 'host1[:port],host2' list; first "
                         "entry becomes the coordinator")
    ap.add_argument("--num-procs", type=int, default=None)
    ap.add_argument("--proc-id", type=int, default=None)
    ap.add_argument("--module", "-m", default=None,
                    help="run a module (python -m semantics) instead of a path")
    ap.add_argument("prog", nargs="?", default=None,
                    help="training program path (ignored with --module)")
    ap.add_argument("prog_args", nargs=argparse.REMAINDER,
                    help="arguments passed through to the program")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    coordinator = args.coordinator
    if coordinator is None and args.mix:
        host, port = parse_mix_option(args.mix)
        coordinator = f"{host}:{port}"

    # JMX-analog scrape endpoint (runtime/metrics_http.py): workers started
    # by bin/hivemall_tpu_daemon.sh opt in via env
    mport = os.environ.get("HIVEMALL_TPU_METRICS_PORT")
    if mport:
        from hivemall_tpu.runtime.metrics_http import serve_metrics

        # loopback unless the operator opts in: the endpoint is
        # unauthenticated, so exposing it beyond the host must be an
        # explicit HIVEMALL_TPU_METRICS_HOST=0.0.0.0 decision (remote
        # scrapers in a fleet set it in conf/cluster_env.sh)
        mhost = os.environ.get("HIVEMALL_TPU_METRICS_HOST", "127.0.0.1")
        srv = serve_metrics(int(mport), host=mhost)
        print(f"[launch] metrics on {mhost}:{srv.server_address[1]}/metrics",
              file=sys.stderr, flush=True)

    joined = init_cluster(coordinator, args.num_procs, args.proc_id)
    import jax

    from hivemall_tpu.runtime.compile_cache import enable_compile_cache

    # after the join: the helper initializes the backend, which must not
    # happen before jax.distributed.initialize
    enable_compile_cache()

    print(f"[launch] distributed={'joined' if joined else 'single-process'} "
          f"process={jax.process_index()}/{jax.process_count()} "
          f"local_devices={len(jax.local_devices())} "
          f"global_devices={len(jax.devices())}", file=sys.stderr, flush=True)

    if args.module is None and args.prog is None:
        # nothing to run: behave like runtime.cluster's report-only mode
        return 0
    if args.module is not None:
        sys.argv = [args.module] + ([args.prog] if args.prog else []) \
            + args.prog_args
        runpy.run_module(args.module, run_name="__main__", alter_sys=True)
    else:
        sys.argv = [args.prog] + args.prog_args
        sys.path.insert(0, os.path.dirname(os.path.abspath(args.prog)))
        runpy.run_path(args.prog, run_name="__main__")
    return 0


if __name__ == "__main__":
    sys.exit(main())
