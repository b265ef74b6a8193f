"""Share of the MXU peak the BERT step programs reach in the traced slice:
FLOPs of each padded step from shapes (costs.bert_forward_flops at the
bucket's rows) over the bf16 peak, over the step's device time.  The steps
carry no annotation; the configuration's buckets are matched to the
distinct device programs by their median time.  Bound: compute."""


def read(run):
    if not run.trace or run.peaks is None:
        return None
    g = run.config["graph"]["parameters"]
    buckets = sorted(int(b) for b in str(g["buckets"]).split(","))
    by_module: dict[str, list[float]] = {}
    for p in run.trace["programs"]:
        by_module.setdefault(p["module"], []).append(p["device_s"])
    mods = sorted(by_module, key=lambda m: run.stats.percentile(by_module[m], 50))
    if len(mods) != len(buckets):
        return None
    shape = {"hidden": run.config["hidden_size"], "ffn": run.config["intermediate_size"],
             "n_layers": run.config["num_hidden_layers"], "n_classes": 2}
    flops = sum(
        len(by_module[m]) * run.costs.bert_forward_flops(shape, b, int(g["seq"]))
        for m, b in zip(mods, buckets)
    )
    seconds = sum(sum(v) for v in by_module.values())
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / seconds
