"""Ablation for the fused scaled-int8 matmul-dequant Pallas kernel.

Thin alias over the shared kernel-ablation harness
(``benchmarks/kernel_ablate.py``, which generalized this file's
bf16-vs-xla-vs-pallas protocol to the whole kernel tier) — kept so the
documented command line keeps working. The gate itself is unchanged:
``ops/pallas/int8_matmul.USE_FUSED_INT8_MATMUL`` stays default-off until
the kernel beats the pure-XLA int8 fallback HERE, on the target TPU
generation; off-TPU the harness refuses to run.

Usage: python benchmarks/int8_matmul_ablate.py [--sizes M,K,N[;M,K,N...]]
       [--iters N]
Equivalent to: python benchmarks/kernel_ablate.py --kernel int8_matmul
               [--shapes ...] [--iters N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

try:
    import distkeras_tpu  # noqa: F401  (pip-installed)
except ImportError:  # running from a source checkout: use the repo root
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

# sibling script import: benchmarks/ is on sys.path both under
# `python benchmarks/x.py` and the file-spec import smoke test
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import kernel_ablate  # noqa: E402

DEFAULT_SIZES = ((512, 512, 512), (1024, 1024, 1024), (2048, 2048, 2048))


def ablate(sizes=DEFAULT_SIZES, iters: int = 5):
    """Original entry point, now routed through the shared harness."""
    return kernel_ablate.ablate("int8_matmul", shapes=sizes, iters=iters)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes", default=None,
                    help="semicolon-separated M,K,N triples "
                         "(default 512^3;1024^3;2048^3)")
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()
    sizes = kernel_ablate.parse_shapes(args.sizes) or DEFAULT_SIZES
    for row in ablate(sizes=sizes, iters=args.iters):
        print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
