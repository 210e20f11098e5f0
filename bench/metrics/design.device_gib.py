"""Bytes of the placed design and row vectors on the fullest device, in
GiB (``GLMSolver.device_bytes``).  Layer: the design operators
(``data/design.py``)."""


def read(ctx):
    if not ctx.device_bytes:
        return None
    return max(ctx.device_bytes.values()) / 2 ** 30
