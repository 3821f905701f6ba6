"""Traffic drivers, one module a mix of requests, named by a workload
file's `driver`."""


def bisecting(number: int, first: int, every: int) -> bool:
    """Whether block `number` of a window carries the mix's wrong items:
    block `first`, and where `every` is above 0 every so-many-th after it."""
    since = number - first
    return since == 0 or (since > 0 and every > 0 and since % every == 0)
