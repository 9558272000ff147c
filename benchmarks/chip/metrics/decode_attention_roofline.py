"""Kernel: decode_attention's share of its roofline in the traced window.
For each call, the least time is the larger of its operations over the
peak rate and its bytes over the HBM bandwidth, counting only each active
slot's valid keys; the share is the summed least time over the summed
kernel time, in percent. ``bound`` says which limit the least time
mostly came from."""
from chipbench import costs, kernels


def read(run):
    least = spent = mem = 0.0
    steps = run.window.steps
    for m in run.step_modules("decode"):
        ctx = steps[m.step].ctx
        if not ctx:
            continue
        for op in m.ops:
            if kernels.kernel_of(op.name) != "decode_attention":
                continue
            s = kernels.shapes(op.name)
            t, bound = costs.least_time_s(
                *costs.decode_attention(ctx, s["Hq"], s["Hkv"], s["hd"]),
                run.peak)
            least += t
            mem += t if bound == "memory" else 0.0
            spent += op.dur * 1e-9
    if spent == 0.0:
        return None
    return {"value": 100.0 * least / spent,
            "bound": "memory" if mem >= least / 2 else "compute"}
