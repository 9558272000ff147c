"""Kernel: flash_attention's share of its roofline in the traced window's
prefills: causal pairs of the real prompt (not its padding), least time
the larger of operations over the peak rate and bytes over the HBM
bandwidth, summed over calls, over the summed kernel time, in percent."""
from chipbench import costs, kernels


def read(run):
    least = spent = mem = 0.0
    for m in run.step_modules("prefill"):
        if not m.prompt:
            continue
        for op in m.ops:
            if kernels.kernel_of(op.name) != "flash_attention":
                continue
            s = kernels.shapes(op.name)
            per, bound = costs.least_time_s(
                *costs.flash_attention(m.prompt, s["Hq"], s["Hkv"],
                                       s["hd"]), run.peak)
            least += s["B"] * per
            mem += s["B"] * per if bound == "memory" else 0.0
            spent += op.dur * 1e-9
    if spent == 0.0:
        return None
    return {"value": 100.0 * least / spent,
            "bound": "memory" if mem >= least / 2 else "compute"}
