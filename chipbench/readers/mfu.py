"""Model FLOP/s utilization of a training cell: tokens per second over the
steps' own time, times the model's FLOPs per token (nothing recomputed),
over the chips' published peak."""
from chipbench import yardstick


def read(run):
    f = run["facts"]
    if "step_tok_s" not in f or f["device_kind"] == "cpu":
        return None     # a rehearsal's CPU has no peak and no MFU
    per_token = yardstick.gpt_flops_per_token(f["flops_widths"], f["seq"])
    return yardstick.mfu_pct(f["step_tok_s"], per_token, f["chips"],
                             f["device_kind"])
