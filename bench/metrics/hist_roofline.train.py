"""The histogram tiles kernel's share of its roofline: the least time the
histograms of the traced window's rounds need at the chip's peaks
(`work.hist_work`: each built row's codes and stats read once, each built
node's histogram written once, from the fitted trees' leaf covers) over
the kernel's summed device time."""
import sys

from metrics import kernels as K
from metrics import work as W


def read(run):
    if run.red is None:
        return None
    t = run.red.kernel_s(K.HIST)
    if t <= 0:
        return None
    g, m = run.config["gbdt"], run.config["n_features"]
    k = (run.config["n_outputs"] if g["sketch_method"] == "none"
         else g["sketch_k"])
    total = W.Work(0.0, 0.0)
    for c in run.leaf_covers:
        total = total + W.hist_work(c, m, k, g["n_bins"])
    pct, bound = W.share(total.scale(run.fits), t, run.kind)
    print(f"bench: hist_roofline.train bound by {bound}", file=sys.stderr)
    return pct
