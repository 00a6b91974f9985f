"""``ragged_a2a`` fabric: phase-pipelined traced dispatch whose per-phase
transfer carries **exactly the live envelope bytes per pair**.

Subclasses ``phase_pipelined`` — geometry, admission, per-phase grouped
GEMMs and the combine scatter are shared, so the two fabrics are
numerically identical by construction; only the movement differs.  Where
the parent's emulation ships a full all_to_all-shaped ``[n, ...]``
buffer with one live slot (``(n-1) * envelope[k]`` slots per rank per
phase — the emulation tax), this backend issues one
``jax.lax.ragged_all_to_all`` per phase whose send/recv sizes are zero
for every pair the plan left dark: ``envelope[k]`` slots cross per live
pair, nothing else.  That is the number the bytes bench counts for a
circuit fabric — this backend makes the TPU wire match the model.

Availability: compiled support for ``jax.lax.ragged_all_to_all``
targets TPU.  Off-TPU the backend **falls back to the parent's dense
emulation** — same admission numerics, same results, emulation bytes —
so configs naming ``ragged_a2a`` run everywhere and take the ragged path
on the TPU.  ``REPRO_FORCE_RAGGED=1`` forces the ragged path on any
backend (the multi-device CPU suite does, with the primitive stubbed).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cost_models import phase_dispatch_tokens
from repro.parallel.fabric.base import register_fabric
from repro.parallel.fabric.phase_pipelined import (
    PhasePipelinedFabric,
    _PhaseMeta,
)

# module seam: the multi-device CPU suite swaps in a stub
_RAGGED = jax.lax.ragged_all_to_all


def ragged_available() -> bool:
    """Does this process take the ragged primitive (vs the emulation)?"""
    if os.environ.get("REPRO_FORCE_RAGGED"):
        return True
    return jax.default_backend() == "tpu"


@register_fabric
class RaggedA2AFabric(PhasePipelinedFabric):
    name = "ragged_a2a"
    schedule_kind = "row"
    requires_envelope = True

    # ------------------------------------------------------ phase transfer
    def _ragged_send(self, ctx, flat, dst, send_on, sender, recv_on):
        """One ragged transfer of my whole ``flat`` [rows, ...] block to
        rank ``dst`` (when ``send_on``), receiving the block rank
        ``sender`` aimed at me (when ``recv_on``).  Each rank serves at
        most one peer per phase, so all offsets are 0 and exactly one
        send/recv size is nonzero — the wire carries only live pairs."""
        n = ctx.n
        rows = flat.shape[0]
        peer = jnp.arange(n, dtype=jnp.int32)
        zero = jnp.zeros((n,), jnp.int32)
        send_sizes = jnp.where(
            (peer == dst) & send_on, jnp.int32(rows), 0
        )
        recv_sizes = jnp.where(
            (peer == sender) & recv_on, jnp.int32(rows), 0
        )
        out = jnp.zeros_like(flat)
        return _RAGGED(
            flat, out, zero, send_sizes, zero, recv_sizes,
            axis_name=ctx.axis,
        )

    def _transfer(self, ctx, row, k, region, vregion, meta: _PhaseMeta):
        if not ragged_available():
            return super()._transfer(ctx, row, k, region, vregion, meta)
        n = ctx.n
        e_local, ck, d = region.shape
        ridx = jnp.arange(n, dtype=jnp.int32)
        inv = jnp.zeros((n,), jnp.int32).at[row.perms[k]].set(ridx)
        sender = inv[ctx.me]  # the rank whose phase-k circuit targets me
        serve_on = meta.on_all[k][sender]
        blk = self._ragged_send(
            ctx,
            jnp.where(meta.on_k[k], region, 0).reshape(e_local * ck, d),
            meta.dst_k[k], meta.on_k[k], sender, serve_on,
        ).reshape(e_local, ck, d)
        # ship validity as f32 (bool payloads through collectives are the
        # part most likely to differ across backends), same as the
        # parent's emulation buffer
        vblk = self._ragged_send(
            ctx,
            jnp.where(meta.on_k[k], vregion, False)
            .astype(jnp.float32)
            .reshape(e_local * ck),
            meta.dst_k[k], meta.on_k[k], sender, serve_on,
        ).reshape(e_local, ck)
        return blk, vblk > 0

    def _transfer_back(self, ctx, row, k, y_k, meta: _PhaseMeta):
        if not ragged_available():
            return super()._transfer_back(ctx, row, k, y_k, meta)
        n = ctx.n
        e_local, ck, d = y_k.shape
        ridx = jnp.arange(n, dtype=jnp.int32)
        inv = jnp.zeros((n,), jnp.int32).at[row.perms[k]].set(ridx)
        sender = inv[ctx.me]
        got_any = meta.on_all[k][sender]
        # reverse circuit: processed block back to whoever targeted me;
        # I receive my own tokens from the rank I dispatched to
        back = self._ragged_send(
            ctx,
            jnp.where(got_any, y_k, 0).reshape(e_local * ck, d),
            sender, got_any, meta.dst_k[k], meta.on_k[k],
        )
        return back.reshape(e_local, ck, d)

    # ---------------------------------------------------------- accounting
    def dispatch_tokens(
        self, *, n: int, cap_uniform: int = 0, schedule=None, envelope=None
    ):
        """Exactly the live envelope bytes: per rank, ``envelope[k]``
        slots for each phase the plan has it participate in, zero for
        dark pairs — ``phase_dispatch_tokens(valid, envelope)``.  Always
        <= the parent's dense-emulation count and strictly below the
        monolithic ``a2a`` bucket whenever the plan leaves pairs dark."""
        if schedule is None or envelope is None:
            raise ValueError(
                "ragged_a2a accounting needs the plan's valid mask and "
                "the envelope"
            )
        k = min(schedule.valid.shape[0], len(np.asarray(envelope)))
        return float(
            np.mean(
                phase_dispatch_tokens(
                    schedule.valid[:k], np.asarray(envelope)[:k]
                )
            )
        )
