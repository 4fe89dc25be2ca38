"""Closed loop: ``clients`` clients, each sending its next request as soon
as its last one is answered.  Mix keys: ``clients``."""


def warm(mix: dict, buckets: list[int], pipeline_depth: int) -> list[int]:
    """With at least ``(pipeline_depth + 1) * max_batch`` clients a full
    batch is always queued, so only the largest bucket is dispatched."""
    if mix["clients"] >= (pipeline_depth + 1) * buckets[-1]:
        return buckets[-1:]
    return list(buckets)


def drive(loop, mix: dict) -> list:
    return loop.closed(int(mix["clients"]))
