"""Shared TPU tile-size helpers for the Pallas kernels."""

LANE = 128

_CANDIDATES = (512, 384, 256, LANE)


def pick_block(dim: int, cap: int = 512) -> int:
    """Largest 128-multiple divisor of ``dim`` from the candidate set, not
    exceeding ``cap`` — bigger blocks amortize per-iteration kernel overhead
    while staying inside VMEM tiles."""
    for c in _CANDIDATES:
        if c <= cap and dim % c == 0:
            return c
    return LANE


def divisor_blocks(dim: int) -> list[int]:
    """Every 128-multiple that divides ``dim``, widest first, for a caller
    that weighs them against a budget of its own (``ops/gmm.py``: VMEM);
    ``[dim]`` where none does — a block as wide as the array is always legal."""
    return [b for b in range(dim - dim % LANE, 0, -LANE) if dim % b == 0] or [dim]
