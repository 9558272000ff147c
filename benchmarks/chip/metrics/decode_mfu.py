"""Whole decode step against the chip's peak: operations the decode calls
in the traced window needed for their active slots (weights, head,
attention over each slot's keys; the configuration's reference counts
them) over their device time times the peak bf16 rate, in percent."""


def read(run):
    ref, cfg = run.cell.reference, run.cell.config
    mods = run.step_modules("decode")
    steps = run.window.steps
    mods = [m for m in mods if steps[m.step].ctx]
    if not mods:
        return None
    flops = sum(ref.step_flops(cfg, "decode", steps[m.step].ctx)
                for m in mods)
    secs = sum(m.dur for m in mods) * 1e-9
    return 100.0 * flops / (secs * run.peak["bf16_flops_per_s"])
