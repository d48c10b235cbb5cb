"""Model FLOP/s utilization of the training step: the operations a forward
and backward pass need per token (no recomputation; ``model_flops_per_token``
of the runner) times tokens per second, over the chips' bf16 peak.  The rate
is that of the window's untraced steps, those after the profiler has
stopped, so that neither the profiler's overhead nor the writing of its
trace is counted."""


def read(run):
    tps = run.extra.get("untraced_tokens_per_s")
    fpt = run.extra.get("flops_per_token")
    if not tps or not fpt:
        return None
    return 100.0 * fpt * tps / (run.chips * run.peaks["bf16_flops"])
