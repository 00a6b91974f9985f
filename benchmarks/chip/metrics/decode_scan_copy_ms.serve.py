"""Device milliseconds per decode step in what the layer scan does
itself (ops under the program's ``stack`` scope and under no layer):
each layer's slice of the KV cache and of the weights, and the cache's
write-back, from the trace and the decode program's op-to-scope
table."""

import decode_scopes as S


def read(run):
    return S.scope_ms(run, "stack")
