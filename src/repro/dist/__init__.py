"""``repro.dist`` — the distribution layer.

Two halves (docs/architecture.md §5):

* :mod:`repro.dist.sharding` — logical-axis rule tables mapping model
  dimension names (batch/seq/embed/ffn/…) to mesh axes, resolved to
  ``PartitionSpec``s with divisibility fallback; the ``axis_rules`` /
  ``current_rules`` context pair; ``logical_constraint`` backing
  ``repro.models.common.constrain``.
* :mod:`repro.dist.collectives` — lowers a ``RepairPlan`` to one SPMD
  program over a ``(pod, node)`` mesh: inner-rack aggregation on the
  ``node`` axis only, relayer→collector transfer as collective-permutes
  across ``pod`` whose compiled bytes equal the plan's cross-rack
  accounting (the Eq. (3) claim, checked in HLO).
"""
