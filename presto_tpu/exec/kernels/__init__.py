"""Hand-written Pallas TPU kernels for the scan hot path.

The fused compiler (exec/fused.py, exec/pipeline.py) stays the planner
and fallback; this package holds the kernels it can dispatch to when a
chain is eligible, selected by the `scan.kernel = xla | pallas | auto`
ExecutionConfig knob.  CPU runs execute the same kernels through Pallas
interpret mode (kernels/shim.py, the only sanctioned `interpret=True`
site) so tier-1 tests cover the kernel path.

kernels/join.py lowers the fused chain's probe-side joins into the scan
kernel body (build tables ride as whole-block operands); kernels/
window.py evaluates running window aggregates with the same pairing
prefix scan the compaction step uses.
"""
from .scan_kernel import (DMA_MODES, KERNEL_DECLINE_REASONS,
                          KERNEL_FAMILY_COMPILES, KERNEL_HASH_MAX_SLOTS,
                          KERNEL_SPAN_MAX_GROUPS, SUBTILE_ROWS,
                          build_direct_runner, chain_families, kernel_gate,
                          try_direct_scan_kernel)
from .grouped import build_hash_runner, try_grouped_scan_kernel
from .join import (KERNEL_JOIN_MAX_BUILD_BYTES, plan_join_layout,
                   reserve_build_operands)
from .window import KERNEL_WINDOW_MAX_BYTES, try_window_kernel
from .shim import kernel_interpret

__all__ = [
    "DMA_MODES",
    "KERNEL_DECLINE_REASONS",
    "KERNEL_FAMILY_COMPILES",
    "KERNEL_HASH_MAX_SLOTS",
    "KERNEL_JOIN_MAX_BUILD_BYTES",
    "KERNEL_SPAN_MAX_GROUPS",
    "KERNEL_WINDOW_MAX_BYTES",
    "SUBTILE_ROWS",
    "build_direct_runner",
    "build_hash_runner",
    "chain_families",
    "kernel_gate",
    "plan_join_layout",
    "reserve_build_operands",
    "try_direct_scan_kernel",
    "try_grouped_scan_kernel",
    "try_window_kernel",
    "kernel_interpret",
]
