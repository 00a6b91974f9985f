"""Model facade: embeddings -> stack -> norm -> logits, plus loss and
serving entry points.  Pure-functional; ``Model`` only carries the config
and a default MoE schedule (static ``A2ASchedule`` or traced
``ScheduleTable``) — callers pass ``schedule=`` per call for
recompile-free swaps.

Inputs are dicts so modality frontends stay stubs (DESIGN.md §4):
  tokens      [B, S_tok] int32
  ext_embeds  [B, P, d]  (optional; 'patch'/'frames' frontends, prepended)
  targets     [B, S] int32 (training; -1 = no loss)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import stack
from repro.models.layers import (
    cast,
    dense_apply,
    dense_init,
    embed_apply,
    embed_init,
    rmsnorm_apply,
    rmsnorm_init,
    sinusoidal_pos,
    unembed_apply,
)
from repro.parallel import shard
from repro.scopes import scope


class Model:
    """``schedule`` (constructor default, overridable per call) is one
    static ``A2ASchedule`` shared by every MoE layer, or a traced
    ``ScheduleTable`` with one row per MoE layer — per-layer plans ride
    the stack's ``lax.scan`` on the train, prefill, and decode paths.

    Prefer passing the table as the *call-site* ``schedule=`` argument of
    ``loss``/``forward``/``prefill``/``decode_step``: under ``jax.jit``
    it is then ordinary traced input, so a re-planned table swaps into
    the same executable with zero recompiles (a constructor-held table
    is baked in as a constant — correctness is identical, but every swap
    costs a retrace)."""

    def __init__(self, cfg: ModelConfig, schedule=None):
        self.cfg = cfg
        if isinstance(schedule, (list, tuple)):
            raise TypeError(
                "per-layer schedules are a traced ScheduleTable now "
                "(core.ScheduleTable.from_schedules)"
            )
        self.schedule = schedule

    def with_schedule(self, schedule) -> "Model":
        """A new facade over the same config with a different default
        schedule (params are untouched).  For recompile-free swaps pass
        the schedule per call instead."""
        return Model(self.cfg, schedule)

    def _sched(self, schedule):
        return self.schedule if schedule is None else schedule

    @property
    def n_moe_layers(self) -> int:
        cfg = self.cfg
        return sum(cfg.ffn_kind(l) == "moe" for l in range(cfg.n_layers))

    # ------------------------------------------------------------- params
    def init(self, key: jax.Array) -> dict:
        cfg = self.cfg
        k_e, k_s, k_h = jax.random.split(key, 3)
        params = {
            "embed": embed_init(k_e, cfg.vocab_size, cfg.d_model),
            "stack": stack.stack_init(k_s, cfg),
            "ln_f": rmsnorm_init(cfg.d_model),
        }
        if not cfg.tie_embeddings:
            params["head"] = dense_init(k_h, cfg.d_model, cfg.vocab_size)
        return params

    # ------------------------------------------------------------ forward
    def _embed(self, params, tokens, ext_embeds=None, *, offset=0):
        cfg = self.cfg
        with scope("embed"):
            x = embed_apply(params["embed"], tokens)
            if ext_embeds is not None:
                x = jnp.concatenate([cast(ext_embeds), x], axis=1)
            if cfg.pos_embedding == "sinusoidal":
                x = x + sinusoidal_pos(x.shape[1], cfg.d_model, offset=offset)[None]
            return shard(x, "batch", None, "embed")

    def _logits(self, params, x):
        cfg = self.cfg
        with scope("logits"):
            x = rmsnorm_apply(params["ln_f"], x, eps=cfg.norm_eps)
            if cfg.tie_embeddings:
                logits = unembed_apply(params["embed"], x)
            else:
                logits = dense_apply(params["head"], x).astype(jnp.float32)
            return shard(logits, "batch", None, "vocab")

    def forward(self, params, tokens, ext_embeds=None, *, schedule=None):
        """Training/eval forward: full-sequence logits [B, S, V] (f32)."""
        x = self._embed(params, tokens, ext_embeds)
        x = stack.stack_train(
            params["stack"], self.cfg, x, self._sched(schedule)
        )
        return self._logits(params, x)

    def _hidden(
        self, params, tokens, ext_embeds=None, *,
        collect_stats=False, schedule=None,
    ):
        x = self._embed(params, tokens, ext_embeds)
        return stack.stack_train(
            params["stack"], self.cfg, x, self._sched(schedule),
            collect_stats=collect_stats,
        )

    def loss(self, params, batch: dict, *, schedule=None) -> jax.Array:
        """Mean next-token CE over positions with targets >= 0.

        The [B, S, V] logits are never materialized: CE runs over sequence
        chunks with rematerialization (bwd recomputes each chunk's logits),
        bounding loss memory at [B, S/nc, V/tp] — essential for 150k-vocab
        models at 4k sequence lengths."""
        hidden = self._hidden(
            params, batch["tokens"], batch.get("ext_embeds"), schedule=schedule
        )
        return self._ce(params, hidden, batch["targets"])

    def loss_and_stats(self, params, batch: dict, *, schedule=None):
        """``loss`` plus the per-layer MoE stats pytree: ``routing``
        ``[n_moe_layers, n_src, E]`` realized counts — the controller
        loop's observation (aux output; host-fetched off the critical
        path) — and ``dropped`` ``[n_moe_layers, n_src]`` admitted-but-cut
        token counts."""
        hidden, stats = self._hidden(
            params, batch["tokens"], batch.get("ext_embeds"),
            collect_stats=True, schedule=schedule,
        )
        return self._ce(params, hidden, batch["targets"]), stats

    def _ce(self, params, hidden, targets) -> jax.Array:
        if hidden.shape[1] != targets.shape[1]:  # frontend prefix: no loss
            pad = hidden.shape[1] - targets.shape[1]
            targets = jnp.concatenate(
                [jnp.full((targets.shape[0], pad), -1, targets.dtype), targets],
                axis=1,
            )
        s = hidden.shape[1]
        nc = 8 if s % 8 == 0 else 1

        def chunk_terms(h_c, t_c):
            logits = self._logits(params, h_c)
            mask = (t_c >= 0).astype(jnp.float32)
            safe = jnp.maximum(t_c, 0)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
            return ((logz - gold) * mask).sum(), mask.sum()

        if nc == 1:
            nll, cnt = chunk_terms(hidden, targets)
            return nll / jnp.maximum(cnt, 1.0)
        b, _, d = hidden.shape
        h_c = hidden.reshape(b, nc, s // nc, d).transpose(1, 0, 2, 3)
        t_c = targets.reshape(b, nc, s // nc).transpose(1, 0, 2)

        def step(carry, xs):
            nll, cnt = jax.checkpoint(chunk_terms)(*xs)
            return (carry[0] + nll, carry[1] + cnt), None

        (nll, cnt), _ = jax.lax.scan(step, (0.0, 0.0), (h_c, t_c))
        return nll / jnp.maximum(cnt, 1.0)

    # ------------------------------------------------------------ serving
    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16) -> dict:
        return stack.stack_cache(self.cfg, batch, max_len, dtype)

    def prefill(self, params, tokens, caches, ext_embeds=None, *, schedule=None):
        """Process the prompt, fill caches.  Returns (last-token logits,
        caches, prompt_len)."""
        x = self._embed(params, tokens, ext_embeds)
        x, caches = stack.stack_prefill(
            params["stack"], self.cfg, x, caches, self._sched(schedule)
        )
        logits = self._logits(params, x[:, -1:, :])
        return logits[:, 0], caches

    def decode_step(
        self, params, token, caches, step, *,
        schedule=None, collect_stats=False, live=None,
    ):
        """One decode step.  token: [B] int32; step: scalar position or a
        ``[B]`` per-slot position vector (continuous batching — each
        batch slot decodes at its own depth; see ``attn.attn_decode``).

        With ``collect_stats`` additionally returns the per-layer MoE
        stats pytree (``routing`` ``[n_moe_layers, n_src, E]`` realized
        counts / ``dropped``; None for MoE-free configs) — the serving
        controller's observation signal.  ``live`` ([B] bool, optional)
        masks vacated batch slots out of the counts so garbage tokens in
        a static-shape decode batch never register as expert demand."""
        cfg = self.cfg
        step = jnp.asarray(step, jnp.int32)
        with scope("embed"):
            x = embed_apply(params["embed"], token[:, None])
            if cfg.pos_embedding == "sinusoidal":
                if step.ndim == 1:
                    pe = jax.vmap(
                        lambda o: sinusoidal_pos(1, cfg.d_model, offset=o)
                    )(step)  # [B, 1, d]
                    x = x + pe
                else:
                    x = x + sinusoidal_pos(1, cfg.d_model, offset=step)[None]
            x = shard(x, "batch", None, "embed")
            token_weight = (  # the stack's other input
                None if live is None else live.astype(jnp.float32)[:, None]
            )
        out = stack.stack_decode(
            params["stack"], cfg, x, caches, step, self._sched(schedule),
            collect_stats=collect_stats, token_weight=token_weight,
        )
        if collect_stats:
            x, caches, stats = out
            return self._logits(params, x)[:, 0], caches, stats
        x, caches = out
        logits = self._logits(params, x)
        return logits[:, 0], caches
