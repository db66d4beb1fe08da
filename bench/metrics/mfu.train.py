"""Model FLOP utilization of the traced window, in %: the model FLOPs per
trained token (the benchmark's copy, `bench/flops.py`; recomputation does
not count) times the tokens trained per second of the window, over the
bf16 peak of the chips in use (`bench/peaks.json`)."""


def read(run):
    if not run["tokens"]:
        return None
    rate = run["tokens"] / run["window_s"]
    per_token = run["flops"].train_flops_per_token(run["config"])
    return 100.0 * per_token * rate / (run["chips"]
                                       * run["peaks"]["bf16_flops_per_s"])
