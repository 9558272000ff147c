"""Whole prefill step against the chip's peak: operations the prefill
calls in the traced window needed (the configuration's reference counts
them: causal attention pairs, logits of the last token) over their device
time times the peak bf16 rate, in percent."""


def read(run):
    ref, cfg = run.cell.reference, run.cell.config
    mods = [m for m in run.step_modules("prefill") if m.prompt]
    if not mods:
        return None
    flops = sum(ref.step_flops(cfg, "prefill", [m.prompt]) for m in mods)
    secs = sum(m.dur for m in mods) * 1e-9
    return 100.0 * flops / (secs * run.peak["bf16_flops_per_s"])
