#!/usr/bin/env python3
"""Lower the training steps at fixed shapes and keep each module's text with
debug info stripped (what the compile cache keys on), so that two trees can be
compared step by step: a refactor that claims "the same programs" shows it.

    python3 scripts/step_module_text.py --root <checkout> --out a.json
    python3 scripts/step_module_text.py --root <other>    --out b.json
    python3 scripts/step_module_text.py --compare a.json b.json

`--hashes` writes each module text's sha256 in place of the text: the form
`tests/data/step_module_hashes.json` keeps, which
`tests/test_minibatch_step_structure.py` holds every step of this tree to.

Nothing runs and nothing is allocated: states are `jax.eval_shape` shapes, on
the CPU backend with eight virtual devices (the mesh steps need four). Only
arguments that every tree since PR 26 accepts are passed to the factories,
but for the `.lanes40` variants: the step of a call whose longest row has 39
features, which a tree since PR 35 runs inside `engine.make_cut_step(step,
40)` and an older tree, which has no such function, runs as it is (so these
differ across that line, and nothing else should).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys


def _variants():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    import numpy as np

    from hivemall_tpu.core import engine
    from hivemall_tpu.core.engine import (DELTA_SLOT, make_train_fn,
                                          make_train_step)
    from hivemall_tpu.core.state import init_linear_state
    from hivemall_tpu.models import classifier as C
    from hivemall_tpu.models import regression as R
    from hivemall_tpu.models.ffm import FFMHyper, init_ffm_state, make_ffm_step
    from hivemall_tpu.models.fm import FMHyper, init_fm_state, make_fm_step
    from hivemall_tpu.parallel.ffm_mix import FFMMixTrainer
    from hivemall_tpu.parallel.mesh import make_mesh
    from hivemall_tpu.parallel.mix import MixedReplicas
    from hivemall_tpu.parallel.sharded_train import (FFMShardedTrainer,
                                                     FMShardedTrainer,
                                                     ShardedTrainer)

    S = jax.ShapeDtypeStruct

    def block(rows, width):
        return (S((rows, width), jnp.int32), S((rows, width), jnp.float32),
                S((rows,), jnp.float32))

    def lin_state(rule, dims, dtype, track=False):
        slots = tuple(rule.slot_names) + ((DELTA_SLOT,) if track else ())
        return jax.eval_shape(lambda: init_linear_state(
            dims, use_covariance=rule.use_covariance, slot_names=slots,
            global_names=rule.global_names, dtype=dtype))

    def cut(step):   # what a call with 39 features a row does to its step
        wrap = getattr(engine, "make_cut_step", None)
        return wrap(step, 40) if wrap else step

    out = {}

    # the linear engine: the cells' step (2^28 bf16, batch_local), its dense
    # arm at a small table, the scan, and the rule shapes that branch inside
    # the step (derive_w, pre_batch globals, covariance + hyper, slots)
    arow = (C.AROW, {"r": 0.1})
    linear = {
        "arow": arow,
        "scw1": (C.SCW1, {"phi": 1.0, "c": 1.0}),
        "pa1": (C.PA1, {"c": 1.0}),
        "adagrad_rda": (C.ADAGRAD_RDA,
                        {"eta": 0.1, "lambda": 1e-6, "scale": 100.0}),
        "adagrad_regr": (R.ADAGRAD_REGR,
                         {"eta": 1.0, "eps": 1.0, "scale": 100.0}),
        "pa1a_regr": (R.PA1A_REGR, {"c": 1.0, "epsilon": 0.01}),
    }
    for name, (rule, hyper) in linear.items():
        for arm, dims, dtype in (("batch_local", 1 << 28, jnp.bfloat16),
                                 ("batch_local_f32", 1 << 25, jnp.float32),
                                 ("dense", 1 << 20, jnp.float32)):
            step = make_train_step(rule, hyper, mode="minibatch")
            out[f"engine.minibatch.{name}.{arm}"] = step.lower(
                lin_state(rule, dims, dtype), *block(1024, 64))
        out[f"engine.scan.{name}"] = make_train_step(
            rule, hyper, mode="scan").lower(
                lin_state(rule, 1 << 20, jnp.float32), *block(4096, 64))
    for name in ("arow", "adagrad_rda"):
        rule, hyper = linear[name]
        for arm, dims, dtype in (("batch_local", 1 << 28, jnp.bfloat16),
                                 ("dense", 1 << 20, jnp.float32)):
            fn = make_train_fn(rule, hyper, mode="minibatch",
                               track_deltas=True)
            out[f"engine.minibatch.{name}.{arm}.track_deltas"] = jax.jit(
                fn).lower(lin_state(rule, dims, dtype, True),
                          *block(1024, 64))
        out[f"engine.scan.{name}.track_deltas"] = jax.jit(make_train_fn(
            rule, hyper, mode="scan", track_deltas=True)).lower(
                lin_state(rule, 1 << 20, jnp.float32, True), *block(256, 64))
    # the cells' steps on Criteo's 39 features: AROW at 2^28 (replay and
    # text), AdaGradRDA at 2^29, and a small table, where the cut lane count
    # moves the shape rule's choice (2^24: dense on 64 lanes, batch_local on 40)
    for name, arm, dims, dtype in (
            ("arow", "batch_local", 1 << 28, jnp.bfloat16),
            ("adagrad_rda", "batch_local_2p29", 1 << 29, jnp.bfloat16),
            ("arow", "f32_2p24", 1 << 24, jnp.float32)):
        rule, hyper = linear[name]
        out[f"engine.minibatch.{name}.{arm}.lanes40"] = cut(make_train_step(
            rule, hyper, mode="minibatch")).lower(
                lin_state(rule, dims, dtype), *block(1024, 64))

    # FM: the cell's step (k=10 in 16 lanes), k=8 (no pad lane), the scan,
    # with regression and adareg
    va = S((1024,), jnp.float32)
    for k in (10, 8):
        for cls in (True, False):
            for adareg in (False, True):
                h = FMHyper(factors=k, classification=cls, adareg=adareg)
                st = jax.eval_shape(lambda: init_fm_state(1 << 23, h))
                tag = f"k{k}.{'c' if cls else 'r'}" \
                    f"{'.adareg' if adareg else ''}"
                out[f"fm.minibatch.{tag}"] = make_fm_step(
                    h, "minibatch").lower(st, *block(1024, 64), va)
                out[f"fm.minibatch.{tag}.nojit"] = jax.jit(make_fm_step(
                    h, "minibatch", jit=False)).lower(st, *block(1024, 64),
                                                      va)
    h = FMHyper(factors=10, classification=True)
    out["fm.scan.k10"] = make_fm_step(h, "scan").lower(
        jax.eval_shape(lambda: init_fm_state(1 << 23, h)),
        *block(4096, 64), S((4096,), jnp.float32))
    out["fm.minibatch.k10.c.lanes40"] = cut(make_fm_step(
        h, "minibatch")).lower(
            jax.eval_shape(lambda: init_fm_state(1 << 23, h)),
            *block(1024, 64), va)

    # FFM: mini-batch with and without -row_chunk at two table sizes (the
    # tags name the two arms the step had before PR 32), and the scan
    for tag, v_bits, rows in (("packed", 16, 256), ("split", 22, 16)):
        fh = FFMHyper(factors=4, num_features=1 << 16, num_fields=16,
                      v_dims=1 << v_bits)
        fst = jax.eval_shape(lambda: init_ffm_state(fh))
        fblk = (S((rows, 8), jnp.int32), S((rows, 8), jnp.float32),
                S((rows, 8), jnp.int32), S((rows,), jnp.float32))
        out[f"ffm.minibatch.{tag}"] = make_ffm_step(fh, "minibatch").lower(
            fst, *fblk)
        out[f"ffm.minibatch.{tag}.row_chunk"] = make_ffm_step(
            fh, "minibatch", row_chunk=rows // 4).lower(fst, *fblk)
    out["ffm.scan"] = make_ffm_step(fh, "scan").lower(fst, *fblk)

    # -mix: the replicated step and the round, four replicas
    devs = jax.devices()[:4]
    for name, dtype, dims in (("bf16_2p28", jnp.bfloat16, 1 << 28),
                              ("f32_2p20", jnp.float32, 1 << 20)):
        mr = MixedReplicas(C.AROW, {"r": 0.1}, dims, dtype, devs)
        state = jax.eval_shape(mr.init)
        blk = (S((4 * 1024, 64), jnp.int32), S((4 * 1024, 64), jnp.float32),
               S((4 * 1024,), jnp.float32), S((4,), jnp.int32))
        out[f"mix.replica_step.{name}"] = mr.step.lower(state, *blk)
        out[f"mix.mix_round.{name}"] = mr.mix.lower(state)
        out[f"mix.replica_step.{name}.lanes40"] = cut(mr.step).lower(
            state, *blk)

    # feature_shard: one stripe step each of the engine and FM
    mesh = Mesh(np.array(devs), ("shard",))
    for name in ("arow", "adagrad_rda"):
        rule, hyper = linear[name]
        tr = ShardedTrainer(rule, hyper, 1 << 24, mesh=mesh)
        out[f"sharded.engine.{name}"] = tr._step.lower(
            jax.eval_shape(tr._init_one), *block(1024, 64))
    tr = ShardedTrainer(*arow, 1 << 24, mesh=mesh, mode="scan")
    out["sharded.engine.arow.scan"] = tr._step.lower(
        jax.eval_shape(tr._init_one), *block(256, 64))
    for k in (10, 8):
        ftr = FMShardedTrainer(FMHyper(factors=k, classification=True),
                               1 << 22, mesh=mesh)
        out[f"sharded.fm.k{k}"] = ftr._step.lower(
            jax.eval_shape(ftr._init_fn), *block(1024, 64), va)
    # FFM's stripe step (scatter-adds a row group) and its -mix replicas
    # (the mini-batch step inside shard_map)
    fh = FFMHyper(factors=4, num_features=1 << 16, num_fields=16,
                  v_dims=1 << 20)
    fblk = (S((64, 8), jnp.int32), S((64, 8), jnp.float32),
            S((64, 8), jnp.int32), S((64,), jnp.float32))
    stripes = FFMShardedTrainer(fh, mesh=mesh)
    out["sharded.ffm"] = stripes._step.lower(
        jax.eval_shape(stripes._init_fn), *fblk)
    mtr = FFMMixTrainer(fh, mesh=make_mesh(devices=devs))
    four = lambda a, *lead: S((4,) + lead + a.shape, a.dtype)   # replicas
    out["mix.ffm"] = mtr._step.lower(
        jax.tree.map(four, jax.eval_shape(lambda: init_ffm_state(fh))),
        *(four(a, 2) for a in fblk))   # two blocks a replica
    return out


def dump(root: str, path: str, hashes: bool = False) -> None:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8")
    sys.path.insert(0, os.path.abspath(root))
    texts = {k: low.as_text() for k, low in _variants().items()}
    import hivemall_tpu
    print("lowered from", os.path.dirname(hivemall_tpu.__file__))
    with open(path, "w", encoding="utf-8") as f:
        json.dump({k: digest(t) for k, t in texts.items()} if hashes
                  else texts, f, indent=1 if hashes else None,
                  sort_keys=hashes)
    for k, t in texts.items():
        print(digest(t)[:16], len(t), k)


def digest(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def compare(a_path: str, b_path: str) -> int:
    a, b = (json.load(open(p, encoding="utf-8")) for p in (a_path, b_path))
    bad = sorted(set(a) ^ set(b))
    for k in bad:
        print("only on one side:", k)
    for k in sorted(set(a) & set(b)):
        same = a[k] == b[k]
        print("identical" if same else "DIFFERS  ", k)
        if not same:
            bad.append(k)
    print(f"{len(set(a) & set(b))} steps compared, {len(bad)} differ")
    return 1 if bad else 0


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--root", default=os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    p.add_argument("--out")
    p.add_argument("--compare", nargs=2, metavar=("A", "B"))
    p.add_argument("--hashes", action="store_true",
                   help="write each text's sha256, not the text")
    args = p.parse_args()
    if args.compare:
        return compare(*args.compare)
    if not args.out:
        p.error("--out or --compare")
    dump(args.root, args.out, args.hashes)
    return 0


if __name__ == "__main__":
    sys.exit(main())
