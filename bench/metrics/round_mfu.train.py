"""The whole boosting round's share of the chip's roofline: the least
time the algorithm's work of a round needs at the chip's peaks
(`work.round_work`, from the cell's shapes and the fitted trees' leaf
covers) over the host-clock time per round of the traced window, which
includes every fit's own host work."""
from metrics import work as W


def read(run):
    if run.red is None:
        return None
    conf, g = run.config, run.config["gbdt"]
    sketched = g["sketch_method"] != "none"
    k = g["sketch_k"] if sketched else conf["n_outputs"]
    per_tree = [W.round_work(c, n=conf["n_train"], n_eval=conf["n_eval"],
                             m=conf["n_features"], d=conf["n_outputs"], k=k,
                             depth=g["depth"], n_bins=g["n_bins"],
                             dense_targets=conf["task"] != "multiclass",
                             sketched=sketched)
                for c in run.leaf_covers]
    total = per_tree[0]
    for w in per_tree[1:]:
        total = total + w
    work = total.scale(run.fits)
    pct, _ = W.share(work, run.elapsed, run.kind)
    return pct
