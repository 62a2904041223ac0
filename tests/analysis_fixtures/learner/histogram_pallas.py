"""PERF001 fixture: `argsort` inside registered device hot-path
functions. The basename matches the real hot-path module so the
rules_perf.HOT_PATH_MANIFEST rows apply; host-side helpers that are
not in the manifest must stay exempt, and an explicit line
suppression must downgrade without hiding."""

import jax.numpy as jnp
import numpy as np


def partition_rows(row_slot, num_slots):
    order = jnp.argsort(row_slot)          # manifest entry point: fires
    return order[:num_slots]


def build_histograms_scatter(bins, row_slot):
    def sweep(s):
        return np.argsort(s)               # nested helper: covered
    return bins[sweep(row_slot)]


def _host_side_bin_boundaries(values):
    # NOT in the manifest: host-side setup (runs once per Dataset, not
    # once per level) may sort freely
    return np.argsort(values)


def build_histograms_pallas(bins, row_slot):
    # the sanctioned oracle shape: visible, auditable suppression
    order = jnp.argsort(row_slot)  # tpulint: disable=PERF001
    return bins[order]


def partition_table(table, row_slot):
    # the stream partition's entry point is registered too: the kernel
    # moves the rows, and a sort that comes back beside it fires (the
    # oracle's stays where it is, suppressed, in partition_rows)
    return table[jnp.argsort(row_slot)]
