"""Model step: the least time of the useful work of the traced steps at
the chip's peaks over the traced window.  Useful work counts bound slots
only: their quantized linear layers (2 K N per token, int8 peak), and at
the bf16 peak their attention over the live context and a tied LM head.
The numerator is work that must be done, so the share cannot pass 100%.
The work is counted by the configuration's work module, ``run.work``."""


def read(run):
    t = run.trace
    if t is None or not run.traced_steps:
        return None
    tokens = sum(s["bound"] for s in run.traced_steps)
    context = sum(s["context"] for s in run.traced_steps)
    least = run.work.useful_least_time(run.model, tokens, context, run.peaks)
    return 100.0 * least / t["window_s"]
