"""Pallas TPU kernels for hot ops the XLA autofuser leaves on the table.

Every kernel here follows the groupnorm lesson (DESIGN.md §6): shape
`fits()` predicates, interpret-mode parity tests on CPU, and — for the
newer kernels — a default-OFF module flag until a cell of `BENCHMARK.json`
measured on the chip (parent against change) shows the kernel winning.

:func:`kernel_registry` is the join point for the roofline report's
``fix_available`` column (profiling/roofline.py): it maps roofline fix
tags to the in-tree kernel behind them and whether its flag is on, so
the report can say "a fix for this op EXISTS in-tree but is disabled"
instead of only naming the tag.
"""

from __future__ import annotations


def kernel_registry() -> dict:
    """Map roofline fix tags -> status of the in-tree kernel behind them.

    Imports lazily so merely importing the package never pays for (or
    breaks on) any individual kernel module. Each entry:
    ``{"module", "flag", "enabled"}`` — ``enabled`` is the raw ablation
    flag (NOT the and-with-on-tpu dispatch predicate: the report asks
    "is the switch thrown", not "would it dispatch on this host"). A
    kernel that no flag selects (``"flag": None``: the code takes it
    wherever the backend and the shapes allow) is always enabled.
    Tags with no in-tree kernel ("memory-layout", "comms-overlap" — the
    latter is a runner mode, not a kernel) are honestly absent.
    """
    from distkeras_tpu.ops.pallas import flash_attention, int8_matmul

    return {
        "pallas-attention": {
            "module": "distkeras_tpu.ops.pallas.flash_attention",
            "flag": "USE_FLASH_ATTENTION",
            "enabled": flash_attention.USE_FLASH_ATTENTION,
        },
        # the gpt decode step's attention over the rows of the KV pool
        # (PERF.md, PR 38): chosen by backend and shape, not by a switch
        "pallas-decode-attention": {
            "module": "distkeras_tpu.ops.pallas.decode_attention",
            "flag": None,
            "enabled": True,
        },
        # nearest in-tree kernel for the fp8-matmul tag: the fused int8
        # matmul (same MXU-narrow-dtype bet; fp8 proper needs hardware
        # we haven't benched)
        "fp8-matmul": {
            "module": "distkeras_tpu.ops.pallas.int8_matmul",
            "flag": "USE_FUSED_INT8_MATMUL",
            "enabled": int8_matmul.USE_FUSED_INT8_MATMUL,
        },
    }
