"""Device milliseconds per decode step in the expert FFN (ops under the
program's ``moe/expert_ffn`` scope), from the trace and the decode
program's op-to-scope table."""

import decode_scopes as S


def read(run):
    return S.scope_ms(run, "moe/expert_ffn")
