"""Share of the chip's bf16 peak that the training step's programs reach
while they run: model FLOPs per step (the program module's
``work(cfg)["step_flops"]``: forward and backward, recomputation left out,
attention causal) times the step executions in the trace, over
their device time."""


def _is_step(name: str) -> bool:
    return "train_step" in name


def read(rec):
    step_flops = rec.work.get("step_flops")
    if rec.trace is None or not step_flops:
        return None
    secs, n = rec.trace.module_time_s(_is_step)
    if n == 0 or secs <= 0:
        return None
    return 100.0 * step_flops * n / secs / rec.peaks["flops_bf16"]
