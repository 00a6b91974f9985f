"""Plain Mixtral reference, written from the published description.

Decoder layers of RMSNorm -> grouped-query attention with rotary
positions (rotate-half form, ``rope_theta``) -> residual, RMSNorm ->
top-2 sparse MoE of SwiGLU experts, the two gates a softmax over the two
chosen router logits -> residual; a final RMSNorm and an untied LM head.
No expert ever drops a token.  Everything is float32 at full matmul
precision, over whole sequences, with no cache, no batching of requests
and nothing of the program: the weights are regenerated from the seed
by ``weights.leaf`` under the names the benchmark gave them.

``low`` selects the control: the same computation with every matrix
product taken in float8 (e4m3, one scale per row of the activations and
per output column of the weights), the precision below the bfloat16
that the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def dims(cfg: dict) -> dict:
    return {
        "d": cfg["hidden_size"],
        "f": cfg["intermediate_size"],
        "h": cfg["num_attention_heads"],
        "kv": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"],
        "e": cfg["num_local_experts"],
        "k": cfg["num_experts_per_tok"],
        "v": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
        "theta": cfg["rope_theta"],
        "eps": cfg["rms_norm_eps"],
    }


# names of the leaves in the tree the benchmark hands the program
def layer_names(dm: dict) -> dict:
    d, f, e, q, kv = dm["d"], dm["f"], dm["e"], dm["h"] * dm["hd"], dm["kv"] * dm["hd"]
    s = "stack/pos0/"
    return {
        "ln1": (s + "ln1/scale", (d,)),
        "wq": (s + "mixer/q/w", (d, q)),
        "wk": (s + "mixer/k/w", (d, kv)),
        "wv": (s + "mixer/v/w", (d, kv)),
        "wo": (s + "mixer/o/w", (q, d)),
        "ln2": (s + "ln2/scale", (d,)),
        "router": (s + "ffn/router/w", (d, e)),
        "w_gate": (s + "ffn/w_gate", (e, d, f)),
        "w_up": (s + "ffn/w_up", (e, d, f)),
        "w_down": (s + "ffn/w_down", (e, f, d)),
    }


def outer_names(dm: dict) -> dict:
    d, v = dm["d"], dm["v"]
    return {
        "embed": ("embed/table", (v, d)),
        "ln_f": ("ln_f/scale", (d,)),
        "head": ("head/w", (d, v)),
    }


def make(key, names: dict, layer: int, served_dtype) -> dict:
    """f32 leaves, rounded through the dtype the program is given."""

    @jax.jit
    def gen(key):
        return {
            k: W.leaf(key, n, shape, layer).astype(served_dtype).astype(jnp.float32)
            for k, (n, shape) in names.items()
        }

    return gen(key)


# ------------------------------------------------------------------- maths
def _q8(x, axis):
    """Round to float8 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(x, w, low: bool):
    """x [..., a] @ w [a, b]."""
    if low:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x [S, H, D]; pos [S]."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(p, x, dm, low):
    """Causal GQA over one sequence x [S, d], in blocks of queries."""
    s = x.shape[0]
    q_chunk = 512
    while s % q_chunk:
        q_chunk //= 2
    h, kv, hd = dm["h"], dm["kv"], dm["hd"]
    pos = jnp.arange(s)
    q = rope(mm(x, p["wq"], low).reshape(s, h, hd), pos, dm["theta"])
    k = rope(mm(x, p["wk"], low).reshape(s, kv, hd), pos, dm["theta"])
    v = mm(x, p["wv"], low).reshape(s, kv, hd)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    if low:
        k, v = _q8(k, -1), _q8(v, -1)

    def block(qc, qpos):
        if low:
            qc = _q8(qc, -1)
        logits = jnp.einsum("qhd,khd->hqk", qc, k, precision="highest") / np.sqrt(hd)
        logits = jnp.where(pos[None, None, :] <= qpos[None, :, None], logits, -jnp.inf)
        w = jax.nn.softmax(logits, axis=-1)
        if low:
            w = _q8(w, -1)
        return jnp.einsum("hqk,khd->qhd", w, v, precision="highest")

    nq = s // q_chunk
    out = jax.lax.map(
        lambda a: jax.checkpoint(block)(*a),
        (q.reshape(nq, q_chunk, h, hd), pos.reshape(nq, q_chunk)),
    )
    return mm(out.reshape(s, h * hd), p["wo"], low)


def route(p, x, dm, low):
    """Dense [T, E] gate matrix: the softmax of the top-k router logits
    at the chosen experts, 0 elsewhere."""
    logits = mm(x, p["router"], low)
    vals, idx = jax.lax.top_k(logits, dm["k"])
    gates = jax.nn.softmax(vals, axis=-1)
    return jnp.zeros_like(logits).at[jnp.arange(x.shape[0])[:, None], idx].set(gates)


def expert(wg, wu, wd, x, low):
    return mm(jax.nn.silu(mm(x, wg, low)) * mm(x, wu, low), wd, low)


def moe(p, x, dm, low):
    """All experts on one device."""
    gw = route(p, x, dm, low)
    y = jnp.zeros_like(x)
    for e in range(p["w_gate"].shape[0]):
        y = y + gw[:, e : e + 1] * jax.checkpoint(expert, static_argnums=4)(
            p["w_gate"][e], p["w_up"][e], p["w_down"][e], x, low
        )
    return y, gw


def layer(p, x, dm, low):
    """One decoder layer over one sequence x [S, d]; also the [S, E]
    gates."""
    x = x + attention(p, rmsnorm(x, p["ln1"], dm["eps"]), dm, low)
    y, gw = moe(p, rmsnorm(x, p["ln2"], dm["eps"]), dm, low)
    return x + y, gw


# ----------------------------------------------------------------- serving
@functools.partial(jax.jit, static_argnames=("dm", "low"))
def _serve_layer(p, xs, dm, low):
    dm = dict(dm)

    def one(x):
        y, gw = layer(p, x, dm, low)
        return y, gw > 0

    return jax.lax.map(one, xs)


@functools.partial(jax.jit, static_argnames=("dm", "low"))
def _serve_head(p, h, dm, low):
    return mm(rmsnorm(h, p["ln_f"], dict(dm)["eps"]), p["head"], low)


def serve_logits(seed: int, cfg: dict, seqs: np.ndarray, rows: np.ndarray, low=False,
                 lengths=None):
    """Logits [n, V] at ``rows`` ((sequence, position) pairs, [n, 2]) of
    the padded sequences ``seqs`` [B, L]: one layer's weights at a time.
    With ``lengths`` ([B], the real positions of each sequence) also the
    routed choices per layer and expert over those positions, [layers, E]."""
    dm = dims(cfg)
    dmk = tuple(sorted(dm.items()))
    key = W.seed_key(seed)
    outer = make(key, outer_names(dm), 0, jnp.bfloat16)
    x = outer["embed"][jnp.asarray(seqs)]
    real = None
    if lengths is not None:
        real = np.arange(seqs.shape[1])[None, :] < np.asarray(lengths)[:, None]
    load = []
    for l in range(dm["layers"]):
        p = make(key, layer_names(dm), l, jnp.bfloat16)
        x, chosen = _serve_layer(p, x, dmk, low)
        if real is not None:
            load.append(np.asarray(chosen)[real].sum(axis=0))
        del p, chosen
    h = x[jnp.asarray(rows[:, 0]), jnp.asarray(rows[:, 1])]
    logits = np.asarray(_serve_head(outer, h, dmk, low))
    return logits if lengths is None else (logits, np.stack(load))
