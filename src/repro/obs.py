"""Spans and counters of the count path, for whoever is looking.

* :func:`span` is ``jax.profiler.TraceAnnotation``: a named host event
  in the profiler's own trace, on the same clock as the device ops.
  With no trace active it costs one TraceMe check; :func:`spanned` is
  the same span as a decorator.  The exact count path records
  ``tc.count`` around ``TriangleEngine.count`` and, inside it,
  ``tc.ingest`` (``from_edges``), ``tc.pack`` (``from_edges_batch``),
  ``tc.plan_sync`` (the pooled-profile ``device_get``), ``tc.plan_layout``
  (``plan_buckets``), ``tc.probe`` (the probe program's dispatch) and
  ``tc.fetch`` (the result ``device_get``); the server records
  ``serve.flush`` and ``serve.finalize``.  The distributed route
  (Algorithm 2) records ``tc.ingest``, ``tc.shard`` (``shard_edges``
  and the uploads of the shards), in allgather mode ``tc.plan_sync``
  (the ``device_get`` of the per-device profile that
  ``_tc_distributed_front`` measured), ``tc.plan_layout``
  (``plan_from_profile``; ``plan_hedge_rounds`` in ring mode),
  ``tc.probe`` (the dispatch of ``_tc_distributed_probe``; of
  ``_tc_distributed`` in ring mode) and ``tc.fetch``.
* A process-wide counter registry: :func:`incr` adds host-side numbers
  the caller already holds, :func:`counters` is a snapshot, and
  :func:`reset` zeroes it.  The counters of the local route, both added
  by exact plans of one lane (a batch's pooled profile bounds its
  lanes, it does not give each lane's degrees):

  ``probe.entries_gathered``
      list entries the plan's dense gathers read: the plan's
      ``rows × d_cand`` candidates plus, where the backend gathers
      target lists (Pallas), ``rows × d_targ`` targets;
  ``probe.entries_real``
      the real neighbour ids among them (the planned rows' degrees).

  And of the distributed route, added by each count's report:

  ``dist.counts``
      distributed counts made;
  ``dist.plans_profiled``
      those whose horizontal-round plan was measured from the gathered
      block's sublist lengths (allgather mode), not the static plan;
  ``dist.rows_planned``
      rows the devices probed: p × the horizontal-round plan's rows,
      p times that again in ring mode (p rounds);
  ``dist.rows_real``
      p × the horizontal edges: every device probes each once;
  ``dist.entries_planned``
      list entries one device's dense gathers read: the plan's
      ``IntersectPlan.gather_entries``, p times that in ring mode
      (every device gathers as much);
  ``dist.wire_bytes``
      the run's ``CommTally`` over all phases, with its BFS sweeps.

There is no exporter: the profiler, once someone starts it, writes the
spans, and :func:`counters` is the scrape.
"""
from __future__ import annotations

import collections
import functools

from jax.profiler import TraceAnnotation as span
from jax.profiler import annotate_function

__all__ = ["span", "spanned", "incr", "counters", "reset"]

_COUNTERS: collections.Counter = collections.Counter()


def spanned(name: str):
    """Decorator: run the function inside ``span(name)``."""
    return functools.partial(annotate_function, name=name)


def incr(name: str, n=1) -> None:
    """Add ``n`` to the counter ``name``."""
    _COUNTERS[name] += n


def counters() -> dict:
    """A snapshot of every counter."""
    return dict(_COUNTERS)


def reset() -> None:
    """Zero every counter."""
    _COUNTERS.clear()
