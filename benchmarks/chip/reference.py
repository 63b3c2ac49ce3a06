"""Plain reference of the paper's hierarchical FedAvg, for ``correct``.

Imports nothing of the program.  One client at a time: the paper's 1-D CNN
(``lax.conv_general_dilated``, float32, its convolutions and matmuls at the
configuration's stated precision, ``Federation.precision``), Adam(1e-3)
with a fresh state every local epoch, then each edge's FedAvg
weighted by client data size and the cloud's FedAvg weighted by edge data
size (paper eqs. 4-9).  Batch indices come from the same numpy stream the
engines consume, drawn in the same order, so the reference follows the
program's trajectory up to rounding.

Who trains in an edge round (``Federation.members``):

* unset (full participation, as ``BatchedSyncEngine`` runs it): the round
  first draws one participation uniform per client, then every client with
  data draws its batch indices in client order, and every client trains;
* set: ``members(b, er)`` gives the sorted ids that train in edge round
  ``er`` of cloud round ``b`` (both counted from 1 within each ``run``
  call, as ``StreamSyncEngine`` keys its cohorts).  No participation
  uniform is drawn; each member with data draws its batch indices in
  ascending id order, as ``StreamCohortPlan.draw`` consumes the stream, and
  only members train.  The round's work and host loops are O(cohort), never
  O(clients).

Either way each edge averages the clients that trained under it, weighted
by data size; an edge with no such client keeps its model, and one whose
clients all hold no data gets a zero model.  An empty client's loss counts
as 0 in the round's mean.  The cloud weights each edge by the data of all
clients attached to it, members or not.  ``Federation.resumes`` says
whether each ``run`` call starts from the previous call's end model (the
engine resumes) or from the initial model (the engine restarts).

``dtype=jnp.bfloat16`` runs the same arithmetic in bfloat16 (parameters,
data, Adam moments, at the chip's default matmul precision): the precision
below the configuration's float32, the control that has to come out not
correct.  ``fault=`` plants one fault in the reference put in the
program's place (see ``FAULTS``).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# Faults planted in the reference put in the program's place:
#   half_batch   -- every local step takes the mean loss over half its batch
#   half_clients -- every other client of an edge round's list (by position)
#                   is left out of its edge's average
FAULTS = ("half_batch", "half_clients")

BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)

# A ``Federation.precision`` for a backend that lacks the chip's default
# matmul precision (calibrate.py --emulate, on the CPU): every convolution
# and matmul operand of the forward pass rounded to bfloat16, products
# summed in float32, as one bfloat16 pass of the chip's matrix unit does.
EMULATED_DEFAULT = "bf16_operands"


@dataclasses.dataclass(frozen=True)
class CNN:
    """Widths of the paper's CNN (conv k -> relu -> pool2, twice; dense; dense)."""

    in_channels: int
    n_classes: int
    seq_len: int
    c1: int
    c2: int
    hidden: int
    kernel: int

    @property
    def flat_dim(self) -> int:
        return (self.seq_len // 2 // 2) * self.c2

    def n_params(self) -> int:
        k = self.kernel
        return (k * self.in_channels * self.c1 + self.c1 + k * self.c1 * self.c2 + self.c2
                + self.flat_dim * self.hidden + self.hidden
                + self.hidden * self.n_classes + self.n_classes)


def init_params(key, cfg: CNN) -> Dict:
    """Normal(0, 1/fan_in) weights, zero biases, from one key split four ways."""
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def conv_w(k, cin, cout):
        scale = 1.0 / np.sqrt(cfg.kernel * cin)
        return jax.random.normal(k, (cfg.kernel, cin, cout), jnp.float32) * scale

    def lin_w(k, din, dout):
        return jax.random.normal(k, (din, dout), jnp.float32) / np.sqrt(din)

    return {
        "conv1": {"w": conv_w(k1, cfg.in_channels, cfg.c1), "b": jnp.zeros((cfg.c1,))},
        "conv2": {"w": conv_w(k2, cfg.c1, cfg.c2), "b": jnp.zeros((cfg.c2,))},
        "fc1": {"w": lin_w(k3, cfg.flat_dim, cfg.hidden), "b": jnp.zeros((cfg.hidden,))},
        "fc2": {"w": lin_w(k4, cfg.hidden, cfg.n_classes), "b": jnp.zeros((cfg.n_classes,))},
    }


def _pool2(h):
    n = h.shape[1] - h.shape[1] % 2
    return jnp.max(h[:, :n].reshape(h.shape[0], n // 2, 2, h.shape[2]), axis=2)


def logits(params, x, emulate: bool = False):
    """x: (B, L, C) -> (B, classes).  ``emulate``: operands rounded to
    bfloat16 (``EMULATED_DEFAULT``)."""
    r = (lambda a: a.astype(jnp.bfloat16).astype(a.dtype)) if emulate else (lambda a: a)

    def conv(h, p):
        out = jax.lax.conv_general_dilated(
            r(h), r(p["w"]), window_strides=(1,), padding="SAME",
            dimension_numbers=("NWC", "WIO", "NWC"))
        return jax.nn.relu(out + p["b"])

    h = _pool2(conv(x, params["conv1"]))
    h = _pool2(conv(h, params["conv2"]))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(r(h) @ r(params["fc1"]["w"]) + params["fc1"]["b"])
    return r(h) @ r(params["fc2"]["w"]) + params["fc2"]["b"]


def xent(params, x, y, emulate: bool = False):
    z = logits(params, x, emulate).astype(jnp.float32)
    gold = jnp.take_along_axis(z, y[:, None], axis=-1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=-1) - gold)


def _adam_step(params, grads, m, v, t, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8):
    """Adam in the parameters' dtype; the step's bias corrections are
    worked out in float32 and then rounded to that dtype."""
    dt = jax.tree.leaves(params)[0].dtype
    m = jax.tree.map(lambda mm, g: b1 * mm + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda vv, g: b2 * vv + (1 - b2) * g * g, v, grads)
    t = t.astype(jnp.float32)
    mh = (1.0 / (1.0 - b1 ** t)).astype(dt)
    vh = (1.0 / (1.0 - b2 ** t)).astype(dt)
    new = jax.tree.map(
        lambda p, mm, vv: (p - lr * (mm * mh) / (jnp.sqrt(vv * vh) + eps)).astype(dt),
        params, m, v)
    return new, m, v


@partial(jax.jit, static_argnames=("dtype", "half_batch", "emulate"))
def _client(num, start, xb, yb, w, dtype, half_batch, emulate):
    """Train one client from ``start`` over (epochs, steps, B, ...) batches,
    a fresh Adam each epoch; return ``num + w * trained`` (its FedAvg share,
    in float32) and the mean step loss of its last epoch."""
    params = jax.tree.map(lambda p: p.astype(dtype), start)
    xb = xb.astype(dtype)
    if half_batch:
        xb, yb = xb[:, :, : xb.shape[2] // 2], yb[:, :, : yb.shape[2] // 2]
    loss = jnp.zeros((), jnp.float32)
    for e in range(xb.shape[0]):
        zeros = jax.tree.map(jnp.zeros_like, params)

        def body(carry, batch):
            p, m, v, t = carry
            l, g = jax.value_and_grad(xent)(p, *batch, emulate)
            p, m, v = _adam_step(p, g, m, v, t)
            return (p, m, v, t + 1), l

        (params, _, _, _), ls = jax.lax.scan(
            body, (params, zeros, zeros, jnp.ones((), jnp.int32)), (xb[e], yb[e]))
        loss = ls.astype(jnp.float32).mean()
    num = jax.tree.map(lambda a, p: a + w * p.astype(jnp.float32), num, params)
    return num, loss


@jax.jit
def _mean(num, den):
    return jax.tree.map(lambda a: a / den, num)


def steps_for(n: int, batch: int, max_steps: int) -> int:
    raw = max(1, min(max_steps, -(-n // batch)))
    return next(b for b in BUCKETS if raw <= b) if raw <= BUCKETS[-1] else BUCKETS[-1]


def draw_indices(rng: np.random.Generator, n: int, steps: int, batch: int, epochs: int) -> np.ndarray:
    """(epochs, steps, batch): a permutation per epoch, padded by resampling."""
    out = np.empty((epochs, steps, batch), np.int64)
    need = steps * batch
    for e in range(epochs):
        idx = rng.permutation(n)
        if need > n:
            idx = np.concatenate([idx, rng.integers(0, n, need - n)])
        out[e] = idx[:need].reshape(steps, batch)
    return out


@dataclasses.dataclass
class Federation:
    """What one run of the reference needs, made by the benchmark itself.

    ``shard(cid)`` returns a client's (x, y); ``sizes`` and ``edge_of``
    cover every client (``edge_of`` -1: attached to no edge).  ``members``
    and ``resumes`` are as the module's docstring says; the driver that
    builds the engine sets both.  ``precision`` is the matmul precision of
    local training that the configuration states
    (``jax.default_matmul_precision``); FedAvg is elementwise float32."""

    cfg: CNN
    shard: Callable[[int], tuple]
    sizes: np.ndarray
    edge_of: np.ndarray
    n_edges: int
    edge_rounds: int
    epochs: int
    batch: int
    max_steps: int
    precision: str
    cloud_weights: np.ndarray = None
    members: Optional[Callable[[int, int], np.ndarray]] = None
    resumes: bool = False

    def __post_init__(self):
        if self.cloud_weights is None:
            att = self.edge_of >= 0
            w = np.bincount(self.edge_of[att], weights=self.sizes[att].astype(np.float64),
                            minlength=self.n_edges)
            self.cloud_weights = np.maximum(w, 1.0)


def run_calls(fed: Federation, seed: int, calls: Sequence[int], dtype=jnp.float32,
              fault: Optional[str] = None) -> List[dict]:
    """Follow the engine through ``calls`` successive ``run(r)`` calls, each
    from the initial model or, where ``fed.resumes``, from the previous
    call's end, while the batch-index stream runs on.

    Returns one dict per call: ``start`` and ``end`` global models (trees
    of numpy float64) and the mean local loss of each cloud round."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    rng = np.random.default_rng(seed)
    init = init_params(jax.random.PRNGKey(seed), fed.cfg)
    out = []
    emulate = fed.precision == EMULATED_DEFAULT
    precision = "highest" if emulate else fed.precision
    glob = init
    with jax.default_matmul_precision(precision if dtype == jnp.float32 else "default"):
        for r in calls:
            if not fed.resumes:
                glob = init
            start = glob
            losses = []
            for b in range(1, r + 1):
                edges = [glob] * fed.n_edges
                round_losses = []
                for er in range(1, fed.edge_rounds + 1):
                    edges, ls = _edge_round(fed, rng, edges, dtype, fault, emulate,
                                            _members(fed, rng, b, er))
                    round_losses += ls
                glob = _cloud(fed, edges)
                losses.append(float(np.mean(round_losses)))
            out.append({"start": _host(start), "end": _host(glob), "losses": losses})
    return out


def _members(fed: Federation, rng, b: int, er: int) -> np.ndarray:
    """Edge round ``er`` of cloud round ``b``'s clients that train, sorted."""
    if fed.members is None:
        rng.random(len(fed.sizes))  # the engine's participation draw
        return np.arange(len(fed.sizes))
    members = np.asarray(fed.members(b, er), np.int64)
    if members.ndim != 1 or (np.diff(members) <= 0).any():
        raise ValueError(f"members({b}, {er}) are not sorted distinct ids")
    if len(members) and (np.asarray(fed.edge_of[members]) < 0).any():
        raise ValueError(f"members({b}, {er}) name a client attached to no edge")
    return members


def _edge_round(fed: Federation, rng, edges, dtype, fault, emulate, members):
    idx = {}
    for cid in members:  # the draws run in client order before any training
        n = int(fed.sizes[cid])
        if n:
            idx[cid] = draw_indices(rng, n, steps_for(n, fed.batch, fed.max_steps), fed.batch,
                                    fed.epochs)
    num: Dict[int, object] = {}
    den: Dict[int, float] = {}
    losses = []
    for k, cid in enumerate(members):
        e = int(fed.edge_of[cid])
        if fault == "half_clients" and k % 2:
            continue
        if e not in num:
            num[e] = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), edges[e])
            den[e] = 0.0
        if cid not in idx:  # an empty shard uploads its start model with weight 0
            losses.append(0.0)
            continue
        x, y = fed.shard(cid)
        w = float(fed.sizes[cid])
        num[e], loss = _client(num[e], edges[e], jnp.asarray(x[idx[cid]]),
                               jnp.asarray(y[idx[cid]]), jnp.float32(w), dtype,
                               fault == "half_batch", emulate and dtype == jnp.float32)
        den[e] += w
        losses.append(loss)
    new = list(edges)
    for e, acc in num.items():  # an edge whose members all weigh 0 gets a zero model
        new[e] = _mean(acc, jnp.float32(den[e])) if den[e] else acc
    return new, [float(l) for l in losses]


def _cloud(fed: Federation, edges):
    w = np.asarray(fed.cloud_weights, np.float64)
    w = w / w.sum()
    return jax.tree.map(
        lambda *leaves: sum(jnp.float32(wi) * l.astype(jnp.float32) for wi, l in zip(w, leaves)),
        *edges)


def _host(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)
