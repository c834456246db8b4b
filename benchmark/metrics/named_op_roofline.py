"""The least time the chip could take for the work `params["work"]` names
(`flops.kernel_work`: the harness's table or the one the configuration's
reference module brings; the larger of operations over peak and bytes over
peak bandwidth), over the device time of the operations whose name starts
with `params["prefix"]` on the first chip, a step, in %. The reduction keys
an operation `<category>/<HLO instruction>`, so a Pallas kernel built with
`name=` is found by it: `mosaic/moe_gmm.3`. No trace, no peak (a rehearsal)
or no such operation: None, never 0.
"""
from harness import flops


def read(run, params):
    devices = run["trace"]["devices"] if run.get("trace") else []
    if not devices or not devices[0]["steps"] or run["peak"] is None:
        return None
    seconds = sum(s for key, s in devices[0]["op_s"].items()
                  if key.startswith(params["prefix"]))
    if seconds <= 0:
        return None
    work, nbytes = flops.kernel_work(params["work"], run["cfg"],
                                     run["traffic"], run.get("reference"))
    least, bound = flops.roofline_seconds(work / run["chips"],
                                          nbytes / run["chips"], run["peak"])
    taken = seconds / devices[0]["steps"]
    run["notes"].append("%s: bound by %s (%.3f ms least, %.3f ms taken)"
                        % (params["work"], bound, least * 1e3, taken * 1e3))
    return 100.0 * least / taken
