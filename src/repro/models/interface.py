"""What the fleet simulator reads of a model family.

A model is a frozen (hashable) configuration object with the methods below;
``models.cnn.CNNConfig`` and ``models.deepseek_v3.DeepSeekV3Config``
implement it.  The simulator's engines, the trainer, the evaluation, the
channel model (Alg. 2's model bytes and FLOPs) and the unit space read a
model through these names only.

Attributes:

``compute_paths``
    the device compute paths the model's ``apply`` takes (``"dense"``, and
    ``"block_skip"`` for a model with ``block_unit_masks`` and
    ``block_compute``).
``importances``
    the pruning criteria (``core.importance.METHODS`` keys) the model
    supports; a simulation asking for another is refused.
``fleet_layout``
    how a resident fleet holds and trains the model.  ``"stacked"``: the
    workers' training is one program vmapped over the ``[W, ...]`` stacks,
    and each worker's 0/1 masks are kept at the parameters' shapes.
    ``"lean"``, for a model whose fleet state fills the device: one worker
    trains after another (a ``lax.map``, so a step's activations are one
    worker's, and operations without a batched form on the device, such as
    the grouped matmul of a sparse expert layer, run unbatched), and masks
    are per-unit vectors that broadcast against the parameters.

Methods:

``init(key) -> {path: array}``
    the base model, float32.
``unit_space(params) -> (UnitSpace, unit_map)``
    the prunable units and, per parameter path, the ``(unit layer, axis)``
    pairs that slice it.
``apply(params, x, **compute) -> outputs``
    a batch's outputs; ``compute`` holds ``compute``, ``unit_masks``,
    ``blocks`` and ``interpret`` for the block-skip path, and ``stats`` for
    the activation statistic of the HRank criterion.
``loss(outputs, y) -> scalar``
    the mean training loss of ``apply``'s outputs.
``counters(outputs) -> {name: scalar}``
    device counters of one training step that the fleet sums into
    ``SimResult.counters`` (empty where the model keeps none).
``eval_outputs(params, x) -> array``
    what the evaluation reads back to the host for a test batch.
``hits(outputs, y) -> int | float``
    the samples of a test batch predicted right (each at most 1), on the
    host, from ``eval_outputs``.
``flops_from_shapes(shapes) -> float``
    forward FLOPs per sample of a (possibly reconfigured) model, from its
    parameter shapes.
``unit_scales(params) -> {layer: array}``
    the per-unit scale CIG-BNscalor ranks by; raises ``ValueError`` for a
    model without one.
``default_task(seed)``
    the task a simulation without ``SimConfig.task`` trains on; raises
    ``ValueError`` for a model that needs one given.
"""
from __future__ import annotations

import jax

__all__ = ["worker_map"]


def worker_map(model, fn):
    """``fn`` mapped over the leading (worker) axis of all its arguments, as
    the model's ``fleet_layout`` says: ``jax.vmap`` (``"stacked"``) or a
    ``lax.map`` over workers with the same inputs and outputs (``"lean"``)."""
    if model.fleet_layout == "stacked":
        return jax.vmap(fn)
    return lambda *args: jax.lax.map(lambda a: fn(*a), args)
