"""TPU kernels: predicates, scoring, gang allocation, fair share, victims."""

from .fit import (group_fit_mask, pod_count_mask, resource_le,  # noqa: F401
                  selector_mask, static_predicate_mask, taint_mask)
from .score import (ScoreWeights, balanced_allocation_score,  # noqa: F401
                    binpack_score, least_requested_score,
                    most_requested_score, node_score)
from .allocate import gang_allocate  # noqa: F401

