"""Expert layer of a ``glm4_moe_lite`` configuration: experts with at least
one token in a decode step's layer, over the ``n_routed_experts`` there are
— ``moe_experts_touched_pct``'s reading (``moeExpertsTouchedTotal`` over
``moeLayerStepsTotal`` x experts, between the ``/statusz`` scrapes at the
window's edges) under this family's key for the count.  It is the share of
the expert weights a decode step reads."""
from benchmark.metrics.moe_experts_touched_pct import moved


def read(rec, variant=None):
    experts = rec["cell"]["config"].get("n_routed_experts")
    touched, steps = moved(rec, "moeExpertsTouchedTotal"), moved(rec, "moeLayerStepsTotal")
    if not experts or not touched or not steps:
        return None
    return 100.0 * touched / (steps * experts)
