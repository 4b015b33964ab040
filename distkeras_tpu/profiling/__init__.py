"""Op-level compute attribution: cost model, roofline classifier, and
device time by program scope.

Extends the attribution ladder one level below ``profile.phase.*`` (PR 10):
from "compute is the residual" to *which HLO op* inside the compiled step
holds the headroom and whether it is memory-, compute- or latency-bound —
the decision input for the ROADMAP item-1 candidates (Pallas attention,
real fp8, psum/overlap co-tuning). ``scopes`` says which
``jax.named_scope`` each instruction of a serving executable was traced
under and sums a profiler trace's device seconds by it. See DESIGN.md §21.

Layering: this package MAY import jax (it reads compiled executables), so
nothing under ``health/`` or ``telemetry.py`` may import it. Results flow
the other way — as ``profile.op.*`` metrics through the registry and as a
digest stamped onto the flight recorder.
"""

from distkeras_tpu.profiling.cost_model import (  # noqa: F401
    OpCost, OpInventory, op_inventory, parse_hlo_ops, source_inventory)
from distkeras_tpu.profiling.roofline import (  # noqa: F401
    RooflineReport, build_report, classify)
# profiling.scopes is imported by name (it is also ``python -m``'s target)
