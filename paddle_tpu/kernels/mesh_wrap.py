"""Mosaic-safe execution of Pallas kernels under multi-device meshes.

Real-TPU finding (round-5, tools/aot_check.py PT_AOT_MULTICHIP): GSPMD
cannot auto-partition Mosaic custom calls — compiling a dp/sp-meshed
program whose lowering contains a Pallas kernel fails with
"NotImplementedError: Mosaic kernels cannot be automatically
partitioned. Please wrap the call in a shard_map." The CPU test mesh
never sees this (interpret-mode kernels are ordinary XLA ops), and a
single chip never does either (nothing to partition) — so the fused
kernels worked everywhere except the one place that matters for
multi-chip: the real TPU SPMD compile.

The fix implemented here: at op-lowering time, when the executor
compiles over a multi-device mesh, every fused-kernel call is wrapped
in a shard_map over ALL the mesh's (non-manual) axes with canonical
dim->axis specs:

  * dims that an axis shards evenly get that axis name (dp on batch,
    sp on sequence, mp on heads) — the kernel runs on its local shard,
    which is exactly right for row-independent kernels (layer_norm,
    softmax-CE) and for batch/head-parallel attention;
  * everything else is replicated w.r.t. the manual axes — shard_map
    inserts the gather, so ANY GSPMD input sharding stays correct
    (at worst wasteful, never wrong).

Inside an already-manual region (the pipeline schedule's manual-pp
shard_map) with auto axes remaining, nesting another partial-manual
shard_map is not attempted: `mode()` returns "xla" and the op keeps
its XLA fallback there. Fully-manual regions (ring attention, pure-pp
pipelines, MoE expert dispatch) need nothing — per-device code never
auto-partitions.
"""

from __future__ import annotations

import jax


def mode(ctx):
    """('direct'|'wrap'|'xla', mesh, wrap_axes) for a lowering ctx."""
    mesh = getattr(ctx, "mesh", None)
    if mesh is None or mesh.devices.size == 1:
        return "direct", None, ()
    manual = tuple(getattr(ctx, "manual_axes", ()) or ())
    auto = tuple(a for a in mesh.axis_names if a not in manual)
    if not auto:
        return "direct", mesh, ()   # fully manual: already per-device
    if manual:
        return "xla", mesh, ()      # nested partial-manual: don't risk
    return "wrap", mesh, auto


def dim_spec(shape, dim_axes, mesh, axes):
    """PartitionSpec naming axis `dim_axes[d]` on dim d when the axis
    exists in the wrap set and divides that dim; None otherwise."""
    from jax.sharding import PartitionSpec as P

    names = []
    for d in range(len(shape)):
        a = dim_axes.get(d)
        if (a is not None and a in axes
                and shape[d] % dict(mesh.shape)[a] == 0):
            names.append(a)
        else:
            names.append(None)
    return P(*names)


def wrap_call(mesh, axes, fn, in_specs, out_specs):
    """shard_map fn manually over the WHOLE mesh. mode() only returns
    'wrap' outside manual regions, where the wrap set is every mesh
    axis — a partial wrap would leave an auto axis free to
    re-partition the Mosaic call."""
    assert set(axes) == set(mesh.axis_names), (axes, mesh.axis_names)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)
