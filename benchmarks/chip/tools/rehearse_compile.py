"""Compile a cell's programs for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/tools/rehearse_compile.py

Compiles the serving cell's decode step and its largest prefill bucket
for one v5e chip and prints ``memory_analysis()`` of each.  Nothing
runs: no time is measured.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)

import harness as H  # noqa: E402


def _gb(x) -> str:
    return f"{x / 1e9:.2f} GB"


def report(name, compiled) -> None:
    m = compiled.memory_analysis()
    print(
        f"{name}: arguments {_gb(m.argument_size_in_bytes)}, outputs {_gb(m.output_size_in_bytes)}, "
        f"temp {_gb(m.temp_size_in_bytes)}, aliased {_gb(m.alias_size_in_bytes)}",
        flush=True,
    )


def serve(topo) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from repro.serve import ServeEngine
    from runners.common import model_config

    conf = H.load_json(H.HERE, "configs", "mixtral-8x7b.serve.json")
    cfg, _, _ = model_config(conf, tiny=False)
    e = conf["engine"]
    one = SingleDeviceSharding(topo.devices[0])
    from repro.models import Model

    shapes = jax.eval_shape(Model(cfg).init, jax.random.PRNGKey(0))
    params = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, jnp.bfloat16, sharding=one), shapes)
    engine = ServeEngine(
        cfg, params, decode_slots=e["decode_slots"], max_len=e["max_len"],
        buckets=tuple(e["buckets"]), controller=e["controller"],
    )

    def sds(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)

    b = engine.batcher
    args = (params, sds(jnp.asarray(b.token)), sds(engine._caches), sds(jnp.asarray(b.step)),
            sds(jnp.asarray(b.live)))
    if engine.has_controller:
        args += (sds(engine._state),)
    report("serve decode", engine._decode.lower(*args).compile())
    big = max(e["buckets"])
    tok = jax.ShapeDtypeStruct((1, big), jnp.int32, sharding=one)
    report(
        f"serve prefill {big}",
        engine._prefill.lower(params, tok, sds(engine._row_template),
                              schedule=None if engine._prefill_table is None
                              else sds(engine._prefill_table)).compile(),
    )


def main() -> int:
    argparse.ArgumentParser(description=__doc__.split("\n\n")[0]).parse_args()
    H.program_path()
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    )
    serve(topo)
    return 0


if __name__ == "__main__":
    sys.exit(main())
