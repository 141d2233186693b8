"""Share of the chip's bf16 peak that the sequence attention kernel
(splash attention, ``kernels.flash_attention``) reaches while it runs: the
FLOPs of its calls in the trace over their device self time. A forward call
counts the program module's ``work(cfg)["attention_fwd_flops"]``, a fused
backward call (``dkv``, which also writes dQ) ``attention_bwd_flops``; a
separate ``dq`` call, which only a two-pass backward makes, adds its time
and no FLOPs, its work being counted with the ``dkv`` call's."""


def _kind(name: str) -> str | None:
    if not name.startswith("splash_mha"):
        return None
    if "dkv" in name:
        return "dkv"
    return "dq" if "_dq" in name else "fwd"


def read(rec):
    fwd = rec.work.get("attention_fwd_flops")
    bwd = rec.work.get("attention_bwd_flops")
    if rec.trace is None or not fwd or not bwd:
        return None
    flops = secs = 0.0
    for kind, call_flops in (("fwd", fwd), ("dkv", bwd), ("dq", 0.0)):
        s, n = rec.trace.op_time_s(lambda name, k=kind: _kind(name) == k)
        flops += n * call_flops
        secs += s
    if secs <= 0:
        return None
    return 100.0 * flops / secs / rec.peaks["flops_bf16"]
