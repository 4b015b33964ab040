"""The upper reading of a sparse-attention cell's limits: a run of the cell
with a fault planted in the program's selection, which has to come out as
not correct.

    python perf/planted_fault.py --fault <skip|recent|stale|none> \\
        --workload <cell> --seed <n> --seconds <s> --trace 0

``perf/run.py``'s run of that cell, as it is, after one of three edits to
the program in this process (``distkeras_tpu.models.latent_moe``, the
functions a full layer's selection goes through):

- ``skip``: the selection skipped. Every position up to the query's is
  attended, through the forms a layer without an indexer takes (the
  absorbed one over the lanes' whole rows, the expanded one under the
  causal mask).
- ``recent``: the most recent ``index_topk`` positions in place of the
  chosen ones: a valid position's index score is its position.
- ``stale``: a decode step selects before it masks. The index scores of a
  short block keep what lies past the query's position (the bucket's
  padding, a freed slot's stale lines, the row's unwritten end), so such
  lines are chosen and attended: the fault the rule "mask before you
  select" (DESIGN.md section 14) is there for.
- ``none``: nothing planted: the cell as ``perf/run.py`` runs it.

A limit is sound when the faults print ``"correct": false`` in their last
line while ``none`` prints true. Cells whose model is a ``LatentMoELM``
under an indexer (``dots3_note``). It measures nothing and is no part of a
run: a fault's numbers are a broken program's.
"""

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]


def plant(fault: str) -> None:
    import jax.numpy as jnp

    from distkeras_tpu.models import latent_moe
    from distkeras_tpu.ops.cache_rows import gather_rows

    if fault == "skip":
        def every(q, leaf, rows, pos, w_kvb, dims, scale, index,
                  from_zero=False):
            short = q.shape[1] <= latent_moe._ABSORB_MAX_BLOCK
            attend = latent_moe._attend_absorbed if short \
                else latent_moe._attend_expanded
            return attend(q, gather_rows(leaf, rows), pos, w_kvb, dims,
                          scale), (pos + 1).astype(jnp.int32)

        latent_moe._attend_chosen = every
    elif fault == "recent":
        scores_of = latent_moe.index_scores

        def recent(iq, iw, keys, pos):
            s = scores_of(iq, iw, keys, pos)
            return jnp.where(jnp.isinf(s), s,
                             jnp.arange(s.shape[-1], dtype=s.dtype))

        latent_moe.index_scores = recent
    elif fault == "stale":
        scores_of = latent_moe.index_scores

        def unmasked(iq, iw, keys, pos):
            if iq.shape[1] > latent_moe._ABSORB_MAX_BLOCK:
                return scores_of(iq, iw, keys, pos)
            return scores_of(iq, iw, keys,
                             jnp.full_like(pos, keys.shape[1]))

        latent_moe.index_scores = unmasked


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", choices=("skip", "recent", "stale", "none"),
                    required=True)
    args, rest = ap.parse_known_args()
    import run

    plant(args.fault)
    sys.argv = [os.path.join(HERE, "run.py")] + rest
    return run.main()


if __name__ == "__main__":
    sys.exit(main())
