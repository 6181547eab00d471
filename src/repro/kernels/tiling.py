"""Row tiling shared by the bucket kernels.

A TPU block's second-to-last dimension must be a multiple of 8 (the
sublane count) unless it spans the whole array. Bucket counts come from
the sync plan and are arbitrary, so the tile is rounded to a multiple of
8 and the grid runs ``cdiv(nb, tb)`` steps: the last block may hang past
the array, its tail rows are padding on read and dropped on write. Every
kernel here computes each row from that row alone, so padding never
reaches a real row.
"""
from __future__ import annotations

SUBLANES = 8


def row_tile(nb: int, target_rows: int) -> int:
    """Rows per grid step: ``nb`` itself when it is at most one sublane
    group, else the largest multiple of 8 not above ``target_rows`` or
    ``nb``."""
    if nb <= SUBLANES:
        return nb
    return max(SUBLANES, min(target_rows, nb) // SUBLANES * SUBLANES)
