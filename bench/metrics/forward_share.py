"""forward_share (%): the device time under the program's ``fedzo.forward``
scope, the model's forward inside each loss query (``fedzo.query`` less
the flat buffer's ``unflatten``), over chips x the traced window. Empty
for a program that registers no such scope."""
from scope_reduce import program_scopes, scope_share

SCOPE = "fedzo.forward"


def read(ctx):
    if SCOPE not in program_scopes():
        return None
    return scope_share(ctx, SCOPE)
