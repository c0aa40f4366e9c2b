"""Device time inside the slot scan (the `while` spans of the scan
program, `_make_slot_step_batched` / `_vc_batched` as the body), per
simulated node-slot of the traced window, in ns."""


def read(run):
    if run.trace is None:
        return None
    ns = run.trace.scan_ns()
    return ns / run.node_slots if ns and run.node_slots else None
