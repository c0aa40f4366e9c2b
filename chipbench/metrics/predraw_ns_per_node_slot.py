"""Device time of the scan program outside its slot scan (the traffic
pre-draw, `_make_traffic`, and the program's set-up of its carry), per
simulated node-slot of the traced window, in ns."""


def read(run):
    if run.trace is None:
        return None
    ns = run.trace.predraw_ns()
    return ns / run.node_slots if ns and run.node_slots else None
