"""Engine programs: device time of one scan iteration, decode-only or mixed:
the summed duration of the ``jit_mega`` and ``jit_mixed`` module events in the
traced window over (launches x K). It does not tell the two scans apart: a
cell in which a prompt is always waiting (mistral7b.serve.batch) launches the
mixed scan only, and this is then the mixed scan's iteration. A launch at a
smaller K bucket counts as K: the figure is then a little low, and such
launches are rare with every slot full."""
from benchmark.harness import xplane

MODULES = ("jit_mega", "jit_mixed")


def read(run):
    if run.get("trace") is None:
        return None
    durs = xplane.module_durations(run["trace"], MODULES)
    if not durs:
        return None
    return 1000.0 * sum(durs) / (len(durs) * run["megastep_k"])
