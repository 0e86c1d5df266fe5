"""Parallelism utilities — the unified device mesh and its shardings.

This is NEW surface relative to the reference (which had no tensor/sequence
parallelism, SURVEY.md §2.5): one :class:`GraftMesh` abstraction whose
named axes (``dp``/``tp``/``pp``/``sp``) every module family binds against
— executor groups shard batches over ``dp``, ``__shard__`` annotations
split parameters over ``tp``, SequentialModule lowers to the GPipe
schedule over ``pp`` rank sets, ring attention rides ``sp`` — and the
composed train steps (dp×pp, dp×tp×pp) that run them together as one
program. The mental model is the standard TPU recipe: pick a mesh,
annotate shardings, let XLA insert collectives over ICI/DCN.
"""

from .compat import shard_map
from .mesh import (
    GraftMesh,
    as_graft,
    current_graft,
    current_mesh,
    data_parallel_mesh,
    get_mesh,
    make_mesh,
    parse_mesh_spec,
    process_leader_mesh,
    replicate,
    shard_batch,
    with_mesh,
)
from .pipeline_parallel import (
    microbatch,
    pipeline_apply,
    stack_stage_params,
)
from .ring_attention import ring_attention, sequence_parallel_sharding
from .tensor_parallel import (
    collect_shard_specs,
    column_parallel_spec,
    parse_shard_spec,
    row_parallel_spec,
    shard_spec_sharding,
    tp_mlp,
)
