"""The held experts' FFNs' share of their roofline (%): the least time of
a step's ``moe.experts`` work over its device time per step. Every held
expert with a routed row reads its three weight matrices once, and every
routed (token, expert) row on a held expert costs 6 · d · f FLOPs
(``costs_mla.experts_bytes``, ``experts_flops``); the least time is the
larger of bytes over the HBM peak and FLOPs over the bf16 peak. The counts
per step are the ``experts_hit`` and ``local_rows`` counters of the
``serve.readback`` spans (summed over the MoE layers), averaged over the
window's steps."""
import costs_mla
from spans import for_run


def read(ctx):
    s = for_run(ctx)
    n = ctx["counters"].get("steps", 0)
    t = ctx["trace"]["scope_s"].get("moe.experts", 0.0)
    if s is None or not n or not t:
        return None
    back = s["host"].get("serve.readback")
    if back is None or "experts_hit" not in back["args"]:
        return None
    conf = ctx["config"]
    hit = back["args"]["experts_hit"] / back["count"]
    rows = back["args"]["local_rows"] / back["count"]
    least = costs_mla.least_s(costs_mla.experts_bytes(conf, hit),
                              costs_mla.experts_flops(conf, rows),
                              ctx["peaks"])
    return least / (t / n) * 100.0
