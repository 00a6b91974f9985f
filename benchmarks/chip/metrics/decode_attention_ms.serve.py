"""Device milliseconds per decode step in attention (ops under the
program's ``attention`` scope: norm, projections, cache write and the
attention over every slot's cache), from the trace and the decode
program's op-to-scope table."""

import decode_scopes as S


def read(run):
    return S.scope_ms(run, "attention")
