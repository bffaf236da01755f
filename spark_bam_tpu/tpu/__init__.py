"""TPU execution engines (JAX/XLA).

- ``checker``  — the vectorized boundary checker as a jittable window kernel
- ``parser``   — batched record-field extraction + on-device interval filter
- ``inflate``  — host-parallel BGZF inflate feeding device windows
"""

from spark_bam_tpu.tpu.checker import TpuChecker, check_window, make_check_window

__all__ = ["TpuChecker", "check_window", "make_check_window"]
