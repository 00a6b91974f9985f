"""``dense`` fabric: no-A2A expert parallelism (psum combine).

Tokens stay put (replicated over the model axis), are locally grouped by
expert into ``[E, C, d]``, experts (sharded over the model axis) compute
their groups, and the output all-reduce combines.  Comm = one all-reduce
of ``[T, d]`` — no dispatch bytes cross the fabric at all, which is why
this is the strongest *non-decomposition* baseline and the default for
single-device smoke tests.

Doubles as two fallbacks the resolver relies on:

* every mesh backend's **single-device / infeasible-shape fallback**
  (decode steps with S=1, sequences that don't split over the EP axis);
* the **virtual fabric**: handed a traced ``ScheduleTable`` row, it maps
  tokens to virtual sources by contiguous blocks and experts by
  contiguous placement (the controller's single-device convention) and
  clips gates through the shared admission mask exactly as the EP
  backends would — scheduled semantics, drift swaps and the
  zero-recompile property are observable without a mesh.
"""

from __future__ import annotations

import jax.numpy as jnp

from repro.core.hierarchical import HierarchicalTable, check_pod_size
from repro.core.schedule import A2ASchedule, ScheduleTable
from repro.parallel.fabric import geometry as g
from repro.parallel.fabric.base import (
    Fabric,
    FabricContext,
    PackedTokens,
    register_fabric,
)
from repro.parallel.sharding import shard


@register_fabric
class DenseFabric(Fabric):
    name = "dense"
    uses_mesh = False
    schedule_kind = "optional_row"

    def validate_schedule(self, schedule, *, n: int):
        # a static A2ASchedule has no meaning without ppermute phases;
        # ignore it (legacy moe_apply behavior: shared static schedules
        # flow to every layer, dense layers just don't execute them)
        if schedule is None or isinstance(schedule, A2ASchedule):
            return None
        if isinstance(schedule, HierarchicalTable):
            # the virtual fabric serves hierarchical rows too (the
            # single-device parity oracle path): admission reads the
            # pair's summed per-pair caps, the wire mask the pod seam
            if not schedule.is_row:
                raise ValueError(
                    "dense: rejected a full HierarchicalTable — pass "
                    "table.row(l)"
                )
            check_pod_size(schedule.n, schedule.pod_size)
            return schedule
        return super().validate_schedule(schedule, n=n)

    def pack(self, ctx: FabricContext, x_loc, idx, gates) -> PackedTokens:
        m = ctx.moe
        t = x_loc.shape[0]
        row = ctx.schedule
        admitted = None
        if row is not None:
            tok = jnp.arange(t * m.top_k, dtype=jnp.int32) // m.top_k
            src = (tok * row.n) // t  # contiguous virtual source blocks
            gates, admitted = g.admission_mask(
                idx, gates, row, m.n_experts, src=src
            )
        cap = g.bucket_capacity(t, m)
        buf, pos, gate, live = g.group_tokens(
            x_loc, idx.reshape(-1), gates.reshape(-1), m.n_experts, cap,
            admitted=admitted,
        )
        wire = None
        if row is not None:
            # virtual fabric: a slot "crosses the wire" iff its token's
            # contiguous virtual source block differs from its bucket's
            # virtual destination rank — the same src/dst convention the
            # admission mask enforces, so scheduled wire-codec semantics
            # are observable without a mesh (pad slots die via ``live``)
            dst_v = jnp.arange(m.n_experts, dtype=jnp.int32) // (
                m.n_experts // row.n
            )
            src_v = (pos * row.n) // t
            if isinstance(row, HierarchicalTable):
                # two-level virtual fabric: only POD-crossing slots ride
                # the inter wire (same-pod remote slots move on the
                # electrical level the codec never touches)
                wire = live & ~g.same_pod(
                    src_v, dst_v[:, None], row.pod_size
                )
            else:
                wire = live & (src_v != dst_v[:, None])
        if admitted is None:
            admitted = jnp.ones((t * m.top_k,), bool)
        return PackedTokens(buf, pos, gate, live, admitted, wire=wire)

    def dispatch(self, ctx: FabricContext, packed: PackedTokens):
        # capacity dim sharded over the DP axis ('fsdp'->data) so expert
        # work splits across data shards too, not just the expert axis
        buf = shard(packed.buf, "expert", "fsdp", None)
        # grouped-launch metadata: explicit slot validity (real admitted
        # token), NOT the gate sign — a zero-gate admitted slot stays live
        return [(buf, packed.live)], None

    def combine(self, ctx: FabricContext, packed: PackedTokens, state, ys):
        return shard(ys[0], "expert", "fsdp", None)

    def dispatch_tokens(
        self, *, n: int, cap_uniform: int = 0, schedule=None, envelope=None
    ):
        """Zero: no token ever crosses the EP fabric (the price is the
        full ``[T, d]`` activation all-reduce instead, which the bench
        reports separately — it is not a dispatch byte)."""
        return 0.0
