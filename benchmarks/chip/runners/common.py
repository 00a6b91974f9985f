"""Model configuration from a configuration file, shared by the runners.

A configuration file names its plain reference, ``"reference":
"<module>"``, a module of ``reference/``, and the harness reads all that
is particular to the model from that module.  Every reference module
provides:

- ``KEYS``: the published ``config.json`` keys the file must match, under
  the model's own names;
- ``published(cfg) -> dict``: the program's ``ModelConfig`` read back
  under those keys;
- ``implements(cfg) -> str | None``: why the reference does not compute
  ``cfg``, or None where it does;
- ``dims(hf) -> dict``: the shapes ``flops`` counts from, ``e_held`` (the
  experts held on this chip) among them;
- for the serving runner ``serve_logits(seed, hf, seqs, rows, low=False,
  lengths=None)``, for the training runner ``train_readings(seed, hf,
  opt, batches, *, devices, n_ranks, low=False, fault=None)``.
"""

from __future__ import annotations

import dataclasses
import importlib


def model_config(conf: dict, tiny: bool):
    """The program's ``ModelConfig`` for this file (smoke widths with
    ``tiny``), the published-style dict the reference reads, and the
    reference module the file names."""
    from repro.configs import get_config, smoke_config

    where = f"configs/{conf['name']}.json"
    if "reference" not in conf:
        raise SystemExit(f"{where}: names no reference module (key 'reference')")
    try:
        ref = importlib.import_module("reference." + conf["reference"])
    except ModuleNotFoundError as e:
        if e.name != "reference." + conf["reference"]:
            raise
        raise SystemExit(f"{where}: no reference module {conf['reference']!r}") from None
    base = smoke_config(conf["registry"]) if tiny else get_config(conf["registry"])
    cfg = dataclasses.replace(
        base,
        **conf["overrides"],
        moe=dataclasses.replace(base.moe, **conf["moe_overrides"]),
    )
    hf = ref.published(cfg)
    if not tiny:
        wrong = {k: (hf[k], conf[k]) for k in ref.KEYS if hf[k] != conf[k]}
        if wrong:
            raise SystemExit(f"{where}: the program's config differs from the file: {wrong}")
    why = ref.implements(cfg)
    if why is not None:
        raise SystemExit(f"{where}: reference.{conf['reference']}: {why}")
    return cfg, hf, ref
