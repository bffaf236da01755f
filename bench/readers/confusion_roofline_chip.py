"""The confusion step's share of ONE chip's memory roofline, where the step's
rows are shared by several chips.

``mesh.step_device_ms`` is the step's time on every chip at once, so the
bytes held against it are those of the rows one chip holds in a step:
``mesh.rows`` ÷ ``mesh.steps`` ÷ the chips of the run, each row's kernel
window read once and a byte of truth a position beside it read once (what
``confusion_roofline`` holds against a step on one chip, where all the
step's rows are that chip's). Four rows a step on four chips is one row a
chip: a quarter of what four rows on one chip read. Memory bounds the step:
it makes no matrix product.
"""

from __future__ import annotations

from bench.readers import confusion_roofline


def least_bytes(rows_a_step: float, chips: int, kernel_window_bytes: int,
                truth_bytes_per_position: int = 1) -> float:
    """The least bytes one chip moves in a step of ``rows_a_step`` rows
    dealt over ``chips`` chips."""
    return confusion_roofline.least_bytes(
        rows_a_step / chips, kernel_window_bytes, truth_bytes_per_position)


def read(args: dict, sources: dict):
    """The step's share on one chip holding all its rows, over the chips
    that share them (the share is linear in the rows)."""
    chips = sources["device"].get("count")
    share = confusion_roofline.read(args, sources)
    return share / chips if share is not None and chips else None
