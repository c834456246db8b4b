"""Device time of the operations whose name starts with `params["prefix"]`
on the first chip, a step, in ms. The reduction keys an operation as
`<category>/<HLO instruction>`, and a Pallas kernel built with `name=` gives
its instruction that name: `mosaic/flash_fwd.1`. No trace, or no such
operation in it: None.
"""


def read(run, params):
    devices = run["trace"]["devices"] if run.get("trace") else []
    if not devices or not devices[0]["steps"]:
        return None
    seconds = sum(s for key, s in devices[0]["op_s"].items()
                  if key.startswith(params["prefix"]))
    return 1e3 * seconds / devices[0]["steps"] if seconds > 0 else None
