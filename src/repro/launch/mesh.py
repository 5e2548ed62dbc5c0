"""Production mesh construction.

Importing this module never touches jax device state; meshes are built by
functions only (the dry-run sets XLA_FLAGS before any jax import).

Mesh shapes (TPU v5e):
  single-pod:  (16, 16)            axes ("data", "model")    = 256 chips
  multi-pod:   (2, 16, 16)         axes ("pod", "data", "model") = 512 chips

The ``pod`` axis has two personalities, selected by the run config:
  * extra data parallelism (default — global batch shards over pod x data);
  * the MISO replica axis (spatial DMR: each pod holds one replica of the
    trainer state; compare is a cross-pod collective).  See DESIGN.md §4.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType

from repro.distributed.sharding import ShardCtx


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis Auto.

    ``jax.make_mesh`` defaults to Explicit axes, under which the program's
    ``with_sharding_constraint`` calls and the slot writes of spatial
    serving are refused; every mesh in the repo is built here instead."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_ctx(
    mesh,
    *,
    pod_role: str = "data",      # data | replica (spatial DMR) | absent
    fsdp: bool = False,
    embed_strategy: str = "auto",
    vocab_size: int = 0,
    d_model: int = 0,
    **kw,
) -> ShardCtx:
    axes = mesh.axis_names
    if "pod" in axes and pod_role == "data":
        data_axes = ("pod", "data")
    else:
        data_axes = ("data",)
    if embed_strategy == "auto":
        # one-hot matmul embedding when a replicated table would be heavy
        table_bytes = vocab_size * d_model * 2
        embed_strategy = ("onehot" if table_bytes > 512 * 1024 * 1024
                          else "gather")
    return ShardCtx(
        mesh=mesh,
        data_axes=data_axes,
        model_axis="model",
        fsdp_axes=("data",) if fsdp else (),
        embed_strategy=embed_strategy,
        **kw,
    )


def make_spatial_ctx(mesh, **kw) -> ShardCtx:
    """ShardCtx for transitions running INSIDE the spatial-DMR executor's
    cross-pod ``shard_map`` (``core/backend_spatial.py``): the pod axis
    carries the MISO replica axis and is manual there, so the transition's
    own sharding constraints must never mention it.  The executor runs the
    body full-manual, so every mesh axis is marked manual —
    ``ShardCtx.constrain`` then drops to a no-op instead of emitting a
    constraint the manual region would reject."""
    return make_ctx(mesh, pod_role="replica",
                    manual_axes=tuple(mesh.axis_names), **kw)
