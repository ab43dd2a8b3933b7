"""Device time of the EP-SpMV Pallas kernel per served SpMV, in ms.

The kernel's ``pallas_call`` carries no name yet, so its events are found
by what it lowers to: the window's only ``custom-call`` ops (the CG vector
updates lower to fusions)."""


def is_kernel(hlo_text: str) -> bool:
    return " custom-call(" in hlo_text


def read(run):
    if run.trace is None or not run.iterations:
        return None
    sec = run.trace.op_seconds(is_kernel)
    return sec * 1e3 / run.iterations if sec > 0 else None
