"""Matrix Factorization: train_mf_sgd / train_mf_adagrad / train_bprmf,
plus mf_predict / bprmf_predict.

Mirrors the reference MF subsystem (ref: mf/OnlineMatrixFactorizationUDTF.java:92-380,
mf/MatrixFactorizationSGDUDTF.java:33-65, mf/MatrixFactorizationAdaGradUDTF.java:34-125,
mf/BPRMatrixFactorizationUDTF.java:65-416, mf/FactorizedModel.java:45-120):

- rating model  r̂ = mu + Bu + Bi + Pu·Qi  (bias clause optional)
- SGD:      Qi += eta*(err*Pu - lambda*Qi); Pu += eta*(err*Qi - lambda*Pu)
            (both against the pre-update "probe" copies, ref: :280-296)
- AdaGrad:  per-element accumulated squared gradients with the x100 scaling
            trick, eta = eta0/sqrt(eps + G) (ref: MatrixFactorizationAdaGradUDTF.java:111-123)
- BPR:      triple (u, i, j): x_uij = (Bi + Pu·Qi) - (Bj + Pu·Qj),
            dloss in {sigmoid, logistic, lnLogistic};
            Pu += eta*(dloss*(Qi - Qj) - regU*Pu); Qi += eta*(dloss*Pu - regI*Qi);
            Qj += eta*(-dloss*Pu - regJ*Qj); item biases likewise
            (ref: BPRMatrixFactorizationUDTF.java:311-416)

TPU-first: user/item tables are dense [U, k]/[I, k] HBM embedding tables
(replacing IntOpenHashMap<Rating[]>); a training row is two row-gathers, the
update two row-scatter-adds — batched across B rows in minibatch mode. Epoch
replay re-runs staged arrays (replaces the 64KiB NIO disk spill, ref: :92,203).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from ..core.emission import select_rows, table_to_host
from ..ops.convergence import ConversionState
from ..ops.eta import EtaEstimator, get_eta
from ..runtime.metrics import REGISTRY
from ..runtime.tracing import (SCOPE_APPLY, SCOPE_GATHER, SCOPE_RULE,
                               SCOPE_TOUCHED, SPAN_CALL, SPAN_DATA_PREP,
                               SPAN_EMIT, SPAN_EPOCH, SPAN_STAGE, SPAN_SYNC,
                               TRACER)
from ..utils.options import Options
from .base import dispatch_step, init_state_spanned


@struct.dataclass
class MFState:
    P: jnp.ndarray  # [U, k]
    Q: jnp.ndarray  # [I, k]
    Bu: jnp.ndarray  # [U]
    Bi: jnp.ndarray  # [I]
    mu: jnp.ndarray  # []
    P_gg: Optional[jnp.ndarray]  # [U, k] adagrad accumulators (scaled)
    Q_gg: Optional[jnp.ndarray]
    touched_u: jnp.ndarray  # [U] int8
    touched_i: jnp.ndarray  # [I] int8
    step: jnp.ndarray  # [] int32


@dataclass(frozen=True)
class MFHyper:
    factor: int = 10
    lambda_: float = 0.03
    mu: float = 0.0
    update_mean: bool = False
    use_bias: bool = True
    rankinit: str = "random"
    maxval: float = 1.0
    min_init_stddev: float = 0.1
    eta: EtaEstimator = EtaEstimator("invscaling", 0.2, power_t=0.1)
    # adagrad
    adagrad: bool = False
    eps: float = 1.0
    scaling: float = 100.0
    seed: int = 31


def init_mf_state(num_users: int, num_items: int, hyper: MFHyper) -> MFState:
    """A fresh state of `num_users` x `num_items`, made by one program. On a
    TPU a `[N, k]` table is laid out with N minor (`{0,1:T(8,128)}`), so a
    row costs k rounded up to 8 sublanes: 64 B at k = 10, not a 128-lane
    tile."""
    return _init_mf_state(int(num_users), int(num_items), hyper)


@partial(jax.jit, static_argnums=(0, 1, 2))
def _init_mf_state(num_users: int, num_items: int, hyper: MFHyper) -> MFState:
    k = hyper.factor
    key = jax.random.PRNGKey(hyper.seed)
    ku, ki = jax.random.split(key)
    if hyper.rankinit == "gaussian":
        P = jax.random.normal(ku, (num_users, k)) * hyper.min_init_stddev
        Q = jax.random.normal(ki, (num_items, k)) * hyper.min_init_stddev
    else:  # 'random': [0, maxval), where upstream's is [0, maxval / k) (ref: Rating.rand init)
        P = jax.random.uniform(ku, (num_users, k), maxval=hyper.maxval)
        Q = jax.random.uniform(ki, (num_items, k), maxval=hyper.maxval)
    gg = (jnp.zeros((num_users, k)), jnp.zeros((num_items, k))) if hyper.adagrad \
        else (None, None)
    return MFState(
        P=P.astype(jnp.float32), Q=Q.astype(jnp.float32),
        Bu=jnp.zeros((num_users,), jnp.float32),
        Bi=jnp.zeros((num_items,), jnp.float32),
        mu=jnp.asarray(hyper.mu, jnp.float32),
        P_gg=gg[0], Q_gg=gg[1],
        touched_u=jnp.zeros((num_users,), jnp.int8),
        touched_i=jnp.zeros((num_items,), jnp.int8),
        step=jnp.zeros((), jnp.int32),
    )


def make_mf_step(hyper: MFHyper, mode: str = "minibatch",
                 jit: bool = True):
    """Rating-MF block update over (users [B], items [B], ratings [B]).

    A row whose user id is the user table's length is padding: its reads
    are zeros, its writes are dropped and the step counter does not count
    it. A call pads its short last block with such rows, so that every
    block of a call runs one program.

    `minibatch` decides every row of a block against the block's starting
    state and adds the rows' deltas at their ids: a row that repeats a
    user or an item adds its own delta beside the others'. Each table is
    read and written by one gather and one in-place scatter-add of the
    block's rows (written as one `.at[ids]` over the block, not a vmap of
    a row's read, which the TPU compiler serves from a row-major copy of
    the whole table). `scan` is upstream's row at a time."""
    lam = hyper.lambda_

    def deltas(Pu, Qi, bu, bi, ggp, ggq, mu, r, t):
        """The rule on gathered rows (one row, or a block's: `err` and `t`
        then lead the rows' shape). `ggp` / `ggq` are the AdaGrad
        accumulators before the row."""
        eta = hyper.eta.eta(t)
        err = r - (mu + bu + bi + jnp.sum(Pu * Qi, axis=-1))
        e = err[..., None]
        gq = e * Pu - lam * Qi
        gp = e * Qi - lam * Pu
        if hyper.adagrad:
            # scaled accumulator trick (ref: MatrixFactorizationAdaGradUDTF.java:111-123)
            dggp, dggq = gp * (gp / hyper.scaling), gq * (gq / hyper.scaling)
            eta_p = hyper.eta.eta0 / jnp.sqrt(
                hyper.eps + (ggp + dggp) * hyper.scaling)
            eta_q = hyper.eta.eta0 / jnp.sqrt(
                hyper.eps + (ggq + dggq) * hyper.scaling)
            dP, dQ = eta_p * gp, eta_q * gq
        else:
            dP, dQ = eta[..., None] * gp, eta[..., None] * gq
            dggp = dggq = None
        dbu = eta * (err - lam * bu) if hyper.use_bias else None
        dbi = eta * (err - lam * bi) if hyper.use_bias else None
        dmu = eta * err if (hyper.use_bias and hyper.update_mean) else None
        return dP, dQ, dbu, dbi, dmu, dggp, dggq, err * err

    def gather(st: MFState, u, i):
        """The rows the rule reads; zeros at a padding row's ids."""
        get = lambda table, ids: table.at[ids].get(mode="fill", fill_value=0)
        with jax.named_scope(SCOPE_GATHER):
            Pu, Qi = get(st.P, u), get(st.Q, i)
            bu = get(st.Bu, u) if hyper.use_bias else 0.0
            bi = get(st.Bi, i) if hyper.use_bias else 0.0
            ggp = get(st.P_gg, u) if hyper.adagrad else None
            ggq = get(st.Q_gg, i) if hyper.adagrad else None
        return Pu, Qi, bu, bi, ggp, ggq

    def apply(st: MFState, u, i, real, d):
        """The deltas `d` added at the rows' ids in place; a padding row's
        ids are past the tables' ends and its writes are dropped."""
        dP, dQ, dbu, dbi, dmu, dggp, dggq = d
        add = lambda table, ids, x: table.at[ids].add(x, mode="drop")
        with jax.named_scope(SCOPE_APPLY):
            st = st.replace(P=add(st.P, u, dP), Q=add(st.Q, i, dQ))
            if hyper.use_bias:
                st = st.replace(Bu=add(st.Bu, u, dbu), Bi=add(st.Bi, i, dbi))
                if hyper.update_mean:
                    st = st.replace(mu=st.mu + jnp.sum(jnp.where(real, dmu, 0.0)))
            if hyper.adagrad:
                st = st.replace(P_gg=add(st.P_gg, u, dggp),
                                Q_gg=add(st.Q_gg, i, dggq))
        with jax.named_scope(SCOPE_TOUCHED):
            one = jnp.ones(jnp.shape(u), jnp.int8)
            st = st.replace(touched_u=st.touched_u.at[u].set(one, mode="drop"),
                            touched_i=st.touched_i.at[i].set(one, mode="drop"))
        return st.replace(step=st.step + jnp.sum(real, dtype=jnp.int32))

    def scan_step(state: MFState, users, items, ratings):
        def body(st, row):
            u, i, r = row
            real = u < st.P.shape[0]
            t = (st.step + 1).astype(jnp.float32)
            *d, loss = deltas(*gather(st, u, i), st.mu, r, t)
            return apply(st, u, i, real, d), jnp.where(real, loss, 0.0)

        state, losses = jax.lax.scan(body, state, (users, items, ratings))
        return state, jnp.sum(losses)

    def minibatch_step(state: MFState, users, items, ratings):
        real = users < state.P.shape[0]
        ts = (state.step + 1 + jnp.arange(users.shape[0])).astype(jnp.float32)
        rows = gather(state, users, items)
        with jax.named_scope(SCOPE_RULE):
            *d, loss = deltas(*rows, state.mu, ratings, ts)
            loss = jnp.sum(jnp.where(real, loss, 0.0))
        return apply(state, users, items, real, d), loss

    step = scan_step if mode == "scan" else minibatch_step
    # jit=False returns the raw traceable fn for embedding in an outer scan
    # (whole-epoch lax.scan over staged blocks, scripts/bench_mf.py)
    return jax.jit(step, donate_argnums=(0,)) if jit else step


def make_bpr_step(hyper: "BPRHyper", mode: str = "minibatch",
                  jit: bool = True):
    def dloss_fn(x):
        if hyper.loss == "sigmoid":
            return 1.0 / (1.0 + jnp.exp(x))
        if hyper.loss == "logistic":
            s = jax.nn.sigmoid(x)
            return s * (1.0 - s)
        # lnLogistic (default): e^-x / (1 + e^-x) = sigmoid(-x)
        return jax.nn.sigmoid(-x)

    def loss_fn(x):
        if hyper.loss == "lnLogistic":
            return jnp.logaddexp(0.0, -x)  # -ln sigmoid(x)
        return -x  # proxy

    def row_deltas(st: MFState, u, i, j, t):
        eta = hyper.eta.eta(t)
        Pu, Qi, Qj = st.P[u], st.Q[i], st.Q[j]
        bi = st.Bi[i] if hyper.use_bias else 0.0
        bj = st.Bi[j] if hyper.use_bias else 0.0
        x_uij = (bi + jnp.dot(Pu, Qi)) - (bj + jnp.dot(Pu, Qj))
        g = dloss_fn(x_uij)
        dP = eta * (g * (Qi - Qj) - hyper.reg_u * Pu)
        dQi = eta * (g * Pu - hyper.reg_i * Qi)
        dQj = eta * (-g * Pu - hyper.reg_j * Qj)
        dbi = eta * (g - hyper.reg_bias * bi) if hyper.use_bias else 0.0
        dbj = eta * (-g - hyper.reg_bias * bj) if hyper.use_bias else 0.0
        return dP, dQi, dQj, dbi, dbj, loss_fn(x_uij)

    def apply(st, u, i, j, dP, dQi, dQj, dbi, dbj, nb):
        st = st.replace(
            P=st.P.at[u].add(dP),
            Q=st.Q.at[i].add(dQi).at[j].add(dQj),
            touched_u=st.touched_u.at[u].set(1),
            touched_i=st.touched_i.at[i].set(1).at[j].set(1),
            step=st.step + nb,
        )
        if hyper.use_bias:
            st = st.replace(Bi=st.Bi.at[i].add(dbi).at[j].add(dbj))
        return st

    def scan_step(state, users, pos, neg):
        def body(st, row):
            u, i, j = row
            t = (st.step + 1).astype(jnp.float32)
            d = row_deltas(st, u, i, j, t)
            return apply(st, u, i, j, *d[:-1], 1), d[-1]

        state, losses = jax.lax.scan(body, state, (users, pos, neg))
        return state, jnp.sum(losses)

    def minibatch_step(state, users, pos, neg):
        b = users.shape[0]
        ts = (state.step + 1 + jnp.arange(b)).astype(jnp.float32)
        dP, dQi, dQj, dbi, dbj, loss = jax.vmap(
            lambda u, i, j, t: row_deltas(state, u, i, j, t))(users, pos, neg, ts)
        return apply(state, users, pos, neg, dP, dQi, dQj, dbi, dbj, b), jnp.sum(loss)

    step = scan_step if mode == "scan" else minibatch_step
    # jit=False returns the raw traceable fn for embedding in an outer scan
    # (whole-epoch lax.scan over staged blocks, scripts/bench_mf.py)
    return jax.jit(step, donate_argnums=(0,)) if jit else step


@dataclass(frozen=True)
class BPRHyper:
    factor: int = 10
    loss: str = "lnLogistic"
    reg_u: float = 0.0025
    reg_i: float = 0.0025
    reg_j: float = 0.00125
    reg_bias: float = 0.01
    use_bias: bool = True
    rankinit: str = "random"
    maxval: float = 1.0
    min_init_stddev: float = 0.1
    eta: EtaEstimator = EtaEstimator("invscaling", 0.3, power_t=0.1)
    seed: int = 31

    # adapters so init_mf_state can be reused
    @property
    def mu(self):
        return 0.0

    @property
    def adagrad(self):
        return False


@dataclass
class TrainedMFModel:
    state: MFState
    use_bias: bool

    def predict(self, users, items) -> np.ndarray:
        """r̂ = mu + Bu + Bi + Pu·Qi (ref: MFPredictionUDF.java:33)."""
        u = np.asarray(users, dtype=np.int64)
        i = np.asarray(items, dtype=np.int64)
        P = np.asarray(self.state.P)[u]
        Q = np.asarray(self.state.Q)[i]
        out = np.sum(P * Q, axis=-1) + float(self.state.mu)
        if self.use_bias:
            out = out + np.asarray(self.state.Bu)[u] + np.asarray(self.state.Bi)[i]
        return out

    def predict_bpr(self, users, items) -> np.ndarray:
        """BPR score = Bi + Pu·Qi (ref: BPRMFPredictionUDF.java)."""
        u = np.asarray(users, dtype=np.int64)
        i = np.asarray(items, dtype=np.int64)
        out = np.sum(np.asarray(self.state.P)[u] * np.asarray(self.state.Q)[i], axis=-1)
        if self.use_bias:
            out = out + np.asarray(self.state.Bi)[i]
        return out

    def model_rows(self):
        """(idx, Pu, Qi, Bu, Bi, mu) — the reference's per-index emission
        (ref: OnlineMatrixFactorizationUDTF close/forward): the rows of the
        users and of the items a trained rating touched, selected where the
        tables are (`core/emission.select_rows`), so what crosses to the
        host is each side's packed flags and its emitted rows."""
        st = self.state
        with TRACER.span(SPAN_EMIT, args={
                "table_dtype": str(st.P.dtype)}) as emit:
            users, (P, Bu), stats = select_rows(
                st.touched_u, [("P", st.P), ("Bu", st.Bu)])
            items, (Q, Bi), i_stats = select_rows(
                st.touched_i, [("Q", st.Q), ("Bi", st.Bi)])
            for key in ("chunks", "d2h_bytes", "h2d_bytes"):
                stats[key] = stats.get(key, 0) + i_stats.get(key, 0)
            mu = table_to_host(st.mu, "mu", stats)
            emit.set(rows_out=len(users) + len(items),
                     item_rows_out=len(items), **stats)
        REGISTRY.counter("emit", "rows").increment(len(users) + len(items))
        return {"users": (users, P, Bu), "items": (items, Q, Bi),
                "mu": float(mu)}


def _mf_options(bpr: bool = False) -> Options:
    o = Options()
    o.add("k", "factor", True, "Number of latent factors [default: 10]", default=10,
          type=int)
    o.add("iter", "iterations", True, "Iterations [default: 1]",
          default=30 if bpr else 1, type=int)
    o.add("rankinit", None, True, "Init strategy [random, gaussian]", default="random")
    o.add("maxval", "max_init_value", True, "Max initial value [default: 1.0]",
          default=1.0, type=float)
    o.add("min_init_stddev", None, True, "Gaussian init stddev [default: 0.1]",
          default=0.1, type=float)
    o.add("disable_cv", "disable_cvtest", False, "Disable convergence check")
    o.add("cv_rate", "convergence_rate", True, "Convergence rate [default: 0.005]",
          default=0.005, type=float)
    o.add("disable_bias", "no_bias", False, "Turn off bias clause")
    o.add("eta", None, True, "Fixed learning rate", type=float)
    o.add("eta0", None, True, "Initial learning rate", type=float)
    o.add("t", "total_steps", True, "Total steps", type=int)
    o.add("power_t", None, True, "Inverse scaling exponent [default 0.1]",
          default=0.1, type=float)
    o.add("boldDriver", "bold_driver", False, "Bold driver eta")
    o.add("seed", None, True, "Init seed", default=31, type=int)
    o.add("mini_batch", None, True, "Mini batch size [default 1 = exact scan]",
          default=1, type=int)
    o.add("num_users", None, True,
          "Rows of the user table: ids are 0 .. num_users - 1, and every "
          "call of a deployment keeps one state shape [default: the "
          "call's largest user id + 1]", type=int)
    o.add("num_items", None, True,
          "Rows of the item table, as -num_users [default: the call's "
          "largest item id + 1]", type=int)
    if bpr:
        o.add("loss", "loss_function", True,
              "Loss [lnLogistic (default), logistic, sigmoid]", default="lnLogistic")
        o.add("reg", "lambda", True, "Regularization factor [default 0.0025]",
              default=0.0025, type=float)
        o.add("reg_u", "reg_user", True, "User regularization", type=float)
        o.add("reg_i", "reg_item", True, "Positive item regularization", type=float)
        o.add("reg_j", None, True, "Negative item regularization", type=float)
        o.add("reg_bias", None, True, "Bias regularization [default 0.01]",
              default=0.01, type=float)
    else:
        o.add("r", "lambda", True, "Regularization factor [default: 0.03]",
              default=0.03, type=float)
        o.add("mu", "mean_rating", True, "Mean rating [default: 0.0]", default=0.0,
              type=float)
        o.add("update_mean", "update_mu", False, "Update the mean rating")
        o.add("eps", None, True, "AdaGrad eps [default 1.0]", default=1.0, type=float)
        o.add("scale", None, True, "AdaGrad scaling [default 100]", default=100.0,
              type=float)
    return o


def _dims_from(idx, given: Optional[int], what: str = "id") -> int:
    """The table's rows: `given`, which every id must be under, or else the
    largest id + 1."""
    if len(idx) and int(np.min(idx)) < 0:
        raise ValueError(f"a {what} id is negative: {int(np.min(idx))}")
    if given is None:
        return int(np.max(idx)) + 1 if len(idx) else 1
    if len(idx) and int(np.max(idx)) >= given:
        raise ValueError(f"{what} id {int(np.max(idx))} is at or beyond the "
                         f"table's {given} rows (-num_{what}s)")
    return int(given)


def _stage_ratings(users, items, ratings, num_users, num_items, block):
    """The call's ratings as `[blocks, block]` arrays, the last block
    padded with rows past the tables' ends (user `num_users`, item
    `num_items`, rating 0), under a `train.stage` span. The span also counts
    the item lanes whose item appears earlier in the same block
    (`dup_item_lanes`, of `item_lanes`): what the block's sum adds to a row
    it has already added to."""
    with TRACER.span(SPAN_STAGE, args={"form": "arrays"}) as sp:
        u = np.asarray(users, dtype=np.int32).reshape(-1)
        i = np.asarray(items, dtype=np.int32).reshape(-1)
        r = np.asarray(ratings, dtype=np.float32).reshape(-1)
        n = len(u)
        if n == 0 or len(i) != n or len(r) != n:
            raise ValueError(f"{n} users, {len(i)} items and {len(r)} "
                             "ratings: one of each a training row, at least one")
        nu = _dims_from(u, num_users, "user")
        ni = _dims_from(i, num_items, "item")
        pad = -n % block
        blocks = tuple(
            np.concatenate([a, np.full(pad, fill, a.dtype)]).reshape(-1, block)
            for a, fill in ((u, nu), (i, ni), (r, 0)))
        ordered = np.sort(blocks[1], axis=1)
        dup = int(np.count_nonzero((ordered[:, 1:] == ordered[:, :-1])
                                   & (ordered[:, 1:] < ni)))
        sp.set(rows=n, blocks=len(blocks[0]), dup_item_lanes=dup,
               item_lanes=n)
    return blocks, n, nu, ni


def _train_rating_mf(users, items, ratings, options: Optional[str], adagrad: bool,
                     name: str, num_users=None, num_items=None) -> TrainedMFModel:
    """The whole call is one `train.call` span, as `fit_linear`'s is."""
    with TRACER.span(SPAN_CALL, args={
            "entry": "mf", "rule": "adagrad" if adagrad else "sgd"}) as call:
        cl = _mf_options().parse(options, name)
        default_eta0 = 1.0 if adagrad else 0.2
        hyper = MFHyper(
            factor=cl.get_int("k", 10),
            lambda_=cl.get_float("r", 0.03),
            mu=cl.get_float("mu", 0.0),
            update_mean=cl.has("update_mean"),
            use_bias=not cl.has("disable_bias"),
            rankinit=cl.get("rankinit", "random"),
            maxval=cl.get_float("maxval", 1.0),
            min_init_stddev=cl.get_float("min_init_stddev", 0.1),
            eta=get_eta(cl, default_eta0),
            adagrad=adagrad,
            eps=cl.get_float("eps", 1.0),
            scaling=cl.get_float("scale", 100.0),
            seed=cl.get_int("seed", 31),
        )
        mini_batch = cl.get_int("mini_batch", 1)
        mode = "minibatch" if mini_batch > 1 else "scan"
        n_given = len(users)
        # the scan's block is a dispatch's rows: a call of fewer takes its own
        block = mini_batch if mode == "minibatch" else min(8192, max(n_given, 1))
        (ub, ib, rb), n, nu, ni = _stage_ratings(
            users, items, ratings,
            cl.get_int("num_users") if cl.has("num_users") else num_users,
            cl.get_int("num_items") if cl.has("num_items") else num_items, block)
        call.set(rows=n, users=nu, items=ni, factor=hyper.factor,
                 mini_batch=mini_batch, mode=mode, block=block)
        state = init_state_spanned(init_mf_state, nu, ni, hyper)
        call.set(table_dtype=str(state.P.dtype))
        step = make_mf_step(hyper, mode)
        iters = cl.get_int("iter", 1)
        conv = ConversionState(not cl.has("disable_cv"), cl.get_float("cv_rate", 0.005))
        real = np.minimum(block, n - block * np.arange(len(ub)))
        h2d_counter = REGISTRY.counter("train", "h2d_bytes")
        row_counter = REGISTRY.counter("hivemall", f"{name}.examples")
        step_no = 0
        for it in range(max(1, iters)):
            with TRACER.span(SPAN_EPOCH, args={"epoch": it}) as epoch:
                # losses stay on the device through the epoch, as fit_linear's do
                epoch_losses = []
                for j in range(len(ub)):
                    with TRACER.span(SPAN_DATA_PREP) as sp:
                        arrays = (ub[j], ib[j], rb[j])
                        h2d = sum(a.nbytes for a in arrays)
                        sp.set(rows=int(real[j]), width=1, h2d_bytes=h2d)
                    h2d_counter.increment(h2d)
                    state, loss = dispatch_step(step, step_no, state, *arrays)
                    step_no += 1
                    epoch_losses.append(loss)
                    row_counter.increment(int(real[j]))
                with TRACER.span(SPAN_SYNC, args={"fetches": len(epoch_losses)}):
                    epoch_loss = float(np.sum(jax.device_get(epoch_losses)))
                epoch.set(steps=len(epoch_losses))
                call.set(epochs=it + 1)
                conv.incr_loss(epoch_loss)
                if iters > 1 and conv.is_converged(n):
                    break
        return TrainedMFModel(state=state, use_bias=hyper.use_bias)


def train_mf_sgd(users, items, ratings, options: Optional[str] = None, **kw):
    return _train_rating_mf(users, items, ratings, options, False, "train_mf_sgd", **kw)


def train_mf_adagrad(users, items, ratings, options: Optional[str] = None, **kw):
    return _train_rating_mf(users, items, ratings, options, True, "train_mf_adagrad", **kw)


def train_bprmf(users, pos_items, neg_items, options: Optional[str] = None,
                num_users=None, num_items=None) -> TrainedMFModel:
    cl = _mf_options(bpr=True).parse(options, "train_bprmf")
    reg = cl.get_float("reg", 0.0025)
    reg_i = cl.get_float("reg_i") if cl.has("reg_i") else reg
    hyper = BPRHyper(
        factor=cl.get_int("k", 10),
        loss=cl.get("loss", "lnLogistic"),
        reg_u=cl.get_float("reg_u") if cl.has("reg_u") else reg,
        reg_i=reg_i,
        reg_j=cl.get_float("reg_j") if cl.has("reg_j") else reg_i / 2.0,
        reg_bias=cl.get_float("reg_bias", 0.01),
        use_bias=not cl.has("disable_bias"),
        rankinit=cl.get("rankinit", "random"),
        maxval=cl.get_float("maxval", 1.0),
        min_init_stddev=cl.get_float("min_init_stddev", 0.1),
        eta=get_eta(cl, 0.3),
        seed=cl.get_int("seed", 31),
    )
    u = np.asarray(users, dtype=np.int32)
    i = np.asarray(pos_items, dtype=np.int32)
    j = np.asarray(neg_items, dtype=np.int32)
    nu = _dims_from(u, cl.get_int("num_users") if cl.has("num_users")
                    else num_users, "user")
    ni = _dims_from(np.concatenate([i, j]), cl.get_int("num_items")
                    if cl.has("num_items") else num_items, "item")
    mf_hyper = MFHyper(factor=hyper.factor, rankinit=hyper.rankinit,
                       maxval=hyper.maxval, min_init_stddev=hyper.min_init_stddev,
                       seed=hyper.seed)
    state = init_mf_state(nu, ni, mf_hyper)
    mini_batch = cl.get_int("mini_batch", 1)
    mode = "minibatch" if mini_batch > 1 else "scan"
    step = make_bpr_step(hyper, mode)
    iters = cl.get_int("iter", 30)
    conv = ConversionState(not cl.has("disable_cv"), cl.get_float("cv_rate", 0.005))
    block = mini_batch if mode == "minibatch" else 8192
    n = len(u)
    for it in range(max(1, iters)):
        epoch_loss = 0.0
        for s in range(0, n, block):
            e = min(s + block, n)
            state, loss = step(state, u[s:e], i[s:e], j[s:e])
            epoch_loss += float(loss)
        conv.incr_loss(epoch_loss)
        if iters > 1 and conv.is_converged(n):
            break
    return TrainedMFModel(state=state, use_bias=hyper.use_bias)


def mf_predict(Pu, Qi, Bu=0.0, Bi=0.0, mu=0.0) -> float:
    """`mf_predict(Pu, Qi[, Bu, Bi, mu])` (ref: mf/MFPredictionUDF.java:33)."""
    return float(np.dot(np.asarray(Pu), np.asarray(Qi)) + Bu + Bi + mu)


def bprmf_predict(Pu, Qi, Bi=0.0) -> float:
    """`bprmf_predict(Pu, Qi[, Bi])` (ref: mf/BPRMFPredictionUDF.java)."""
    return float(np.dot(np.asarray(Pu), np.asarray(Qi)) + Bi)
