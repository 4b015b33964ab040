"""Sum of the histograms ``over`` as a share of the sum of the histograms
``under``, in percent, or with ``complement`` what is left of 100: seconds
of some phases of a loop over the seconds of the whole loop. Exact sums
since the engine started (``registry_hist``). None where any of them is
missing or empty."""

from readers import registry_hist


def read(ctx, reduced, over: list, under: list, complement: bool = False):
    rows = registry_hist.histograms()
    if not all(rows.get(name, {}).get("count") for name in over + under):
        return None
    share = 100.0 * sum(rows[name]["sum"] for name in over) \
        / sum(rows[name]["sum"] for name in under)
    return 100.0 - share if complement else share
