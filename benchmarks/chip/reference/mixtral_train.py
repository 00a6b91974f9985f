"""Plain Mixtral training reference: the loss of the model the training
cell runs, its gradients, and AdamW steps, written from the published
description and the configuration file.

The forward pass is ``reference.mixtral``'s: RMSNorm, grouped-query
attention with rotary positions, residual, RMSNorm, a top-2 sparse MoE
of SwiGLU experts whose two gates are a softmax over the two chosen
router logits, residual; a final RMSNorm and an untied LM head.  The
loss is the mean next-token cross entropy over every target >= 0.
Gradients come from ``jax.grad``; the optimizer is AdamW as the
configuration's ``optimizer`` states it (global-norm clipping, bias
correction, decoupled decay of every leaf of rank 2 or more as the
program holds it, which includes the layer-stacked norm scales).

Everything is float32 at full matmul precision, over whole sequences;
no expert ever drops a token and nothing of the program is used: the
weights are regenerated from the seed by ``weights.leaf``.  It runs in
blocks so that it fits the cell's chips: attention by query block, the
experts over blocks of tokens, the head over chunks of tokens, each
block under ``jax.checkpoint``; the expert weights and their moments
are split over the chips by expert, the embedding and the head by
vocabulary entry, and the rest is held whole on every chip.

Departures from the published model: one layer of 32 (the configuration
file's cut), random weights, and no auxiliary load-balancing loss (the
program adds none).

``low`` is the control: every matrix product in float8, as in
``reference.mixtral``.  ``fault`` plants a fault of the program into the
reference put in its place: ``no_exchange`` (each token reaches only the
experts of the rank that holds it: a rank holds an equal slice of every
sequence's positions and an equal share of the experts), ``half_batch``
(the loss over the first half of the sequences only).

For the harness (``runners/common.py``) it is the reference a training
configuration names: ``reference.mixtral``'s ``KEYS``, ``published``,
``implements`` and ``dims``, and ``train_readings``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import weights as W
from reference import mixtral as R
from reference.mixtral import KEYS, dims, implements, published  # noqa: F401  (the contract)

EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
TOKEN_BLOCK = 1024
LOSS_CHUNKS = 8


def leaves(dm: dict) -> dict:
    """{key: (name in the program's tree, per-layer shape, stacked)}."""
    out = {k: (n, s, True) for k, (n, s) in R.layer_names(dm).items()}
    out.update({k: (n, s, False) for k, (n, s) in R.outer_names(dm).items()})
    return out


def sharding_of(key: str, mesh: Mesh) -> NamedSharding:
    if key in EXPERT_LEAVES:
        return NamedSharding(mesh, P("x", None, None))
    if key == "embed":
        return NamedSharding(mesh, P("x", None))
    if key == "head":
        return NamedSharding(mesh, P(None, "x"))
    return NamedSharding(mesh, P())


def decayed(key: str, dm: dict) -> bool:
    name, shape, stacked = leaves(dm)[key]
    return stacked or len(shape) >= 2


def _weights(key, dm: dict) -> list[dict]:
    """One dict of f32 leaves per layer, and the outer leaves in a dict
    of their own at the end."""
    spec = leaves(dm)
    inner = [k for k in spec if spec[k][2]]
    return [
        {k: W.leaf(key, spec[k][0], spec[k][1], l) for k in inner} for l in range(dm["layers"])
    ] + [{k: W.leaf(key, spec[k][0], spec[k][1]) for k in spec if not spec[k][2]}]


def per_leaf(dm: dict, fn) -> list[dict]:
    """``fn(key)`` for every leaf, in the layout of the weights."""
    spec = leaves(dm)
    return [{k: fn(k) for k in spec if spec[k][2]} for _ in range(dm["layers"])] + [
        {k: fn(k) for k in spec if not spec[k][2]}
    ]


def init(seed: int, dm: dict, mesh: Mesh) -> list[dict]:
    """The seed's f32 weights, split over the chips."""
    f = jax.jit(functools.partial(_weights, dm=dm),
                out_shardings=per_leaf(dm, lambda k: sharding_of(k, mesh)))
    return f(W.seed_key(seed))


# ------------------------------------------------------------------- model
def _expert(wg, wu, wd, x, low):
    """``reference.mixtral.expert`` with the weights already rounded
    (``low``: to float8 by ``_moe``, once for every block of tokens)."""
    mm = lambda a, w: R.mm(a, w, False) if not low else jnp.matmul(  # noqa: E731
        R._q8(a, -1), w, precision="highest")
    return mm(jax.nn.silu(mm(x, wg)) * mm(x, wu), wd)


def _moe(p, x, dm, low, mask):
    """x [T, d] -> [T, d]: every expert over every block of tokens,
    weighted by the dense gate matrix."""
    t, d = x.shape
    gw = R.route(p, x, dm, low)
    if mask is not None:
        gw = gw * mask
    tb = min(TOKEN_BLOCK, t)
    ws = [p[k] for k in EXPERT_LEAVES]
    if low:  # one scale per output column of each expert's matrix
        ws = [R._q8(w, 1) for w in ws]
    expert = jax.vmap(functools.partial(_expert, low=low), in_axes=(0, 0, 0, None))

    def block(xb, gb):
        ys = expert(*ws, xb)  # [E, tb, d]
        return jnp.einsum("etd,te->td", ys, gb, precision="highest")

    y = jax.lax.map(
        lambda a: jax.checkpoint(block)(*a),
        (x.reshape(t // tb, tb, d), gw.reshape(t // tb, tb, -1)),
    )
    return y.reshape(t, d)


def _layer(p, x, dm, low, mask):
    """x [B, S, d]."""
    b, s, d = x.shape
    h = jax.lax.map(
        jax.checkpoint(lambda xs: R.attention(p, R.rmsnorm(xs, p["ln1"], dm["eps"]), dm, low)), x
    )
    x = x + h
    y = _moe(p, R.rmsnorm(x, p["ln2"], dm["eps"]).reshape(b * s, d), dm, low, mask)
    return x + y.reshape(b, s, d)


def _local_mask(dm: dict, b: int, s: int, n_ranks: int):
    """[B*S, E]: 1 where the expert lives on the rank holding the token."""
    rank = np.arange(s) // (s // n_ranks)
    e_rank = np.arange(dm["e"]) // (dm["e"] // n_ranks)
    m = (rank[:, None] == e_rank[None, :]).astype(np.float32)
    return jnp.asarray(np.tile(m, (b, 1)))


def loss(params, tokens, targets, dm, low=False, fault=None, n_ranks=1):
    *layers, outer = params
    if fault == "half_batch":
        tokens, targets = tokens[: tokens.shape[0] // 2], targets[: targets.shape[0] // 2]
    b, s = tokens.shape
    mask = _local_mask(dm, b, s, n_ranks) if fault == "no_exchange" else None
    x = outer["embed"][tokens]
    for p in layers:
        x = _layer(p, x, dm, low, mask)
    t = b * s
    nc = LOSS_CHUNKS if t % LOSS_CHUNKS == 0 else 1

    def chunk(h, y):
        logits = R.mm(R.rmsnorm(h, outer["ln_f"], dm["eps"]), outer["head"], low)
        keep = (y >= 0).astype(jnp.float32)
        gold = jnp.take_along_axis(logits, jnp.maximum(y, 0)[:, None], axis=-1)[:, 0]
        return jnp.sum((jax.nn.logsumexp(logits, axis=-1) - gold) * keep), jnp.sum(keep)

    nll, cnt = jax.lax.map(
        lambda a: jax.checkpoint(chunk)(*a),
        (x.reshape(nc, t // nc, -1), targets.reshape(nc, t // nc)),
    )
    return jnp.sum(nll) / jnp.maximum(jnp.sum(cnt), 1.0)


# --------------------------------------------------------------- optimizer
def lr_at(step, o: dict):
    """The configuration's schedule: linear warm-up, then cosine to
    ``final_frac`` of the peak."""
    step = jnp.asarray(step, jnp.float32)
    warm = o["peak_lr"] * step / max(o["warmup_steps"], 1)
    prog = jnp.clip(
        (step - o["warmup_steps"]) / max(o["total_steps"] - o["warmup_steps"], 1), 0.0, 1.0
    )
    cos = o["final_frac"] + (1 - o["final_frac"]) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return jnp.where(step < o["warmup_steps"], warm, o["peak_lr"] * cos)


def _adamw(params, grads, mu, nu, step, o, decay):
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    grads = jax.tree.map(lambda g: g * jnp.minimum(1.0, o["clip_norm"] / gnorm), grads)
    mu = jax.tree.map(lambda m, g: o["b1"] * m + (1 - o["b1"]) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: o["b2"] * v + (1 - o["b2"]) * g * g, nu, grads)
    bc1, bc2 = 1 - o["b1"] ** step, 1 - o["b2"] ** step
    lr = lr_at(step, o)

    def upd(p, m, v, dk):
        delta = (m / bc1) / (jnp.sqrt(v / bc2) + o["eps"])
        if dk:
            delta = delta + o["weight_decay"] * p
        return p - lr * delta

    params = jax.tree.map(upd, params, mu, nu, decay)
    return params, mu, nu, grads


def _norms(tree, dm) -> dict:
    """{leaf name: L2 norm}, the expert leaves one norm per expert
    (``name#e``)."""
    spec = leaves(dm)
    *layers, outer = tree
    out = {}
    for l, p in enumerate(layers):
        for k, v in p.items():
            name = f"{spec[k][0]}@{l}"
            if k in EXPERT_LEAVES:
                n = jnp.sqrt(jnp.sum(v * v, axis=tuple(range(1, v.ndim))))
                out.update({f"{name}#{e}": n[e] for e in range(v.shape[0])})
            else:
                out[name] = jnp.sqrt(jnp.sum(v * v))
    for k, v in outer.items():
        out[spec[k][0]] = jnp.sqrt(jnp.sum(v * v))
    return out


def step_fn(dm: dict, opt: dict, *, low: bool = False, fault: str | None = None,
            n_ranks: int = 1):
    """The jitted step: (params, mu, nu, tokens, targets, step number)
    -> (params, mu, nu, loss, {leaf: norm of its clipped gradient})."""
    dmk = tuple(sorted(dm.items()))
    decay = per_leaf(dm, lambda k: decayed(k, dm))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, mu, nu, tokens, targets, step):
        with jax.default_matmul_precision("highest"):
            value, grads = jax.value_and_grad(loss)(
                params, tokens, targets, dict(dmk), low, fault, n_ranks
            )
            params, mu, nu, clipped = _adamw(params, grads, mu, nu, step, opt, decay)
        return params, mu, nu, value, _norms(clipped, dm)

    return train_step


def train_readings(seed: int, cfg: dict, opt: dict, batches: list[dict], *, devices,
                   n_ranks: int, low: bool = False, fault: str | None = None) -> dict:
    """Follow ``len(batches)`` AdamW steps from the seed's weights.

    Returns each step's loss, the norm of each leaf of the first step's
    gradient as the optimizer takes it (after clipping), and the norm of
    each leaf's change over the steps."""
    dm = dims(cfg)
    mesh = Mesh(np.asarray(devices), ("x",))
    train_step = step_fn(dm, opt, low=low, fault=fault, n_ranks=n_ranks)

    @jax.jit
    def change(params, key):
        return _norms(jax.tree.map(jnp.subtract, params, _weights(key, dm)), dm)

    params = init(seed, dm, mesh)
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    rep = NamedSharding(mesh, P())
    losses, first = [], None
    for i, b in enumerate(batches):
        tok = jax.device_put(np.asarray(b["tokens"]), rep)
        tgt = jax.device_put(np.asarray(b["targets"]), rep)
        params, mu, nu, value, g = train_step(params, mu, nu, tok, tgt, np.float32(i + 1))
        losses.append(float(value))
        if first is None:
            first = {k: float(v) for k, v in g.items()}
    moved = {k: float(v) for k, v in change(params, W.seed_key(seed)).items()}
    return {"loss": losses, "grad": first, "change": moved}
