"""Optimizer (``utils/optim.py``): objective-kernel events on the device
per chunk of the traced window — the passes the lockstep L-BFGS took on the
NORMAL path (the lazy stage-1 / stage-2 programs the walk runs), counted in
the trace, not by ``count_evals``'s separate program.  A value-and-gradient
pass is a forward and an adjoint event, so this is events, not passes."""


def read(run):
    walks = run.result.get("traced_walks")
    if not walks or run.trace is None or not run.trace.devices:
        return None
    chunks = len(walks) * run.result["walks"][0]["n_chunks"]
    scope = run.cell.config["objective"]["kernel"]
    return run.trace.scope(scope)["events"] / chunks
