"""The quantize and dequantize work's share of the traced window of a
training cell, in percent: device time of the operations under the
program's ``qdq`` scope in the forward, backward and recomputation, plus
the idle gaps inside the step that end at one of them
(``chipbench.scopes``).  Nothing to read where no such operation ran."""
from chipbench import scopes


def read(ctx):
    part = scopes.of_context(ctx)
    return None if part is None else part.share(part.component_s("qdq"))
