"""The optimizer update's share of the traced window of a training cell,
in percent: device time of every operation under the program's
``optimizer`` scope (clip, moment decode, update, encode; fused kernel or
loop), plus the idle gaps inside the step that end at one of them
(``chipbench.scopes``).  Nothing to read where no such operation ran."""
from chipbench import scopes


def read(ctx):
    part = scopes.of_context(ctx)
    return None if part is None else part.share(part.component_s("optimizer"))
