"""Device milliseconds per training step in the expert FFN (ops under
the program's ``moe/expert_ffn`` scope, forward and backward), from the
trace and the step program's op-to-scope table, averaged over the
chips."""

import train_scopes as S


def read(run):
    return S.scope_ms(run, "moe/expert_ffn")
