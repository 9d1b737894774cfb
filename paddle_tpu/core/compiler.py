"""CompiledProgram / BuildStrategy / ExecutionStrategy.

Reference: python/paddle/fluid/compiler.py:87,160 — wraps a Program with
a BuildStrategy (pass pipeline config) + ExecutionStrategy and builds a
ParallelExecutor over N CUDA devices.

TPU-native redesign: with_data_parallel() attaches a jax Mesh and input
shardings. There is no graph-rewrite pass pipeline — XLA/GSPMD performs
what BuildStrategy's passes did (fusion: fuse_elewise_add_act_ops,
fused_all_reduce; memory reuse; scheduling), so BuildStrategy knobs are
accepted for API parity and mostly advisory.
"""

from __future__ import annotations

from typing import Optional

from . import framework


class BuildStrategy:
    """Knobs accepted for parity with details/build_strategy.h:37.
    Fusion/memory knobs are no-ops (XLA always fuses); reduce_strategy
    selects grad aggregation layout for the distributed executor."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        self.fuse_elewise_add_act_ops = False
        self.fuse_bn_act_ops = False
        self.fuse_all_reduce_ops = False
        self.fuse_all_optimizer_ops = False
        self.enable_inplace = True
        self.memory_optimize = True
        self.sync_batch_norm = False
        self.num_trainers = 1
        self.trainer_id = 0


class ExecutionStrategy:
    """Reference details/execution_strategy.h. Thread counts are
    meaningless under XLA; kept for API parity."""

    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 1
        self.num_iteration_per_run = 1
        self.allow_op_delay = False


class CompiledProgram:
    def __init__(self, program_or_graph, build_strategy: Optional[BuildStrategy] = None):
        if isinstance(program_or_graph, CompiledProgram):
            program_or_graph = program_or_graph._program
        self._program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._mesh = None
        self._in_shardings = None
        # per-STATE-var (parameter) specs for this compile only — kept
        # here, not on the Program's vars, so one with_* choice can't
        # poison a later compile of the same program on another mesh
        self._state_shardings = None
        # extra lowering-context entries (e.g. sp_mode) for this compile
        self._axis_env = None
        # which with_* strategy built _mesh (chaining guard)
        self._strategy = None
        # the ResolvedPartition when with_partitioning built the mesh
        # (report/gauge access; None for the other strategies)
        self._partition = None
        # cache-key fragment (mesh/device fingerprint, sharding tuples)
        # precomputed once for the executor's hot-path dispatch cache
        # instead of per Executor.run call (runtime/dispatch)
        self._frag = None

    def _dispatch_fragment(self):
        """Hashable summary of everything about THIS CompiledProgram
        that the executor's dispatch cache must key on. Built lazily
        after the single with_* strategy ran (the _claim_strategy guard
        makes mesh/shardings immutable from then on), then reused every
        step."""
        frag = self._frag
        if frag is None:
            mesh = self._mesh
            frag = self._frag = (
                (tuple(sorted(dict(mesh.shape).items())),
                 tuple(d.id for d in mesh.devices.flat))
                if mesh is not None else None,
                tuple(sorted((k, tuple(v))
                             for k, v in self._in_shardings.items()))
                if self._in_shardings else None,
                tuple(sorted((k, tuple(v))
                             for k, v in self._state_shardings.items()))
                if self._state_shardings else None,
                tuple(sorted(self._axis_env.items()))
                if self._axis_env else None,
                self._strategy,
            )
        return frag

    def _claim_strategy(self, name: str) -> None:
        """Each compile takes exactly ONE with_* strategy. Chaining
        with_sequence_parallel().with_expert_parallel() used to
        silently keep only the last mesh/shardings (round-4 advisor
        finding); combined meshes are built by the single strategy's
        own dp=... argument instead."""
        if self._strategy is not None:
            raise ValueError(
                f"CompiledProgram: {name} after {self._strategy} — "
                f"strategies are mutually exclusive per compile; use "
                f"the dp= argument of {self._strategy} (or a fresh "
                f"CompiledProgram) for combined meshes")
        self._strategy = name
        # a run BEFORE the strategy may have cached the mesh-less
        # fragment — drop it so the next dispatch re-keys on the real
        # mesh/shardings instead of silently reusing the unsharded
        # executable
        self._frag = None

    def with_data_parallel(
        self,
        loss_name: Optional[str] = None,
        build_strategy: Optional[BuildStrategy] = None,
        exec_strategy: Optional[ExecutionStrategy] = None,
        share_vars_from: Optional["CompiledProgram"] = None,
        places=None,
    ) -> "CompiledProgram":
        """Shard the batch dimension of every data var over all local
        devices. Under pjit this alone reproduces the reference's
        all-reduce data parallelism: XLA inserts the gradient psum from
        the sharding constraint (multi_devices_graph_pass.cc:446's job).
        """
        import jax
        from jax.sharding import Mesh, PartitionSpec as P
        import numpy as np

        self._claim_strategy("with_data_parallel")
        if build_strategy is not None:
            self._build_strategy = build_strategy
        devs = np.array(places_to_devices(places) if places else jax.devices())
        self._mesh = Mesh(devs, ("dp",))
        shardings = {}
        for v in self._program.global_block().vars.values():
            if getattr(v, "is_data", False) and v.shape:
                shardings[v.name] = P(*(("dp",) + (None,) * (len(v.shape) - 1)))
        self._in_shardings = shardings
        return self

    def _axis_mesh(self, axis: str, n: int, dp: int, places):
        """(dp, <axis>) mesh over the first dp*n devices — the shared
        construction for the sp / ep variants."""
        import jax
        from jax.sharding import Mesh
        import numpy as np

        devs = np.array(places_to_devices(places) if places else jax.devices())
        need = n * dp
        if devs.size < need:
            raise ValueError(
                f"{axis} parallel needs dp*{axis}={need} devices, "
                f"have {devs.size}")
        if dp > 1:
            return Mesh(devs[:need].reshape(dp, n), ("dp", axis))
        return Mesh(devs[:n], (axis,))

    def with_partitioning(self, config=None, devices=None,
                          **kwargs) -> "CompiledProgram":
        """The logical-axis-rules partitioner (paddle_tpu.partition):
        resolve a complete sharding assignment — feeds, params,
        optimizer state — from the config's rules table over its mesh,
        and attach it to this compile. Unlike the single-form with_*
        strategies above, one config drives EVERY parallelism the
        rules express at once (dp batch sharding, tp megatron weights,
        ZeRO state) and the same rules serve any mesh shape.

        ``config`` is a ``partition.PartitionConfig`` (or None to build
        one from ``kwargs`` / the ``partition_*`` flags). ``devices``
        optionally pins the device set (defaults to ``jax.devices()``).
        The resolve report is kept on ``self.partition`` and exported
        as ``paddle_partition_*`` gauges."""
        from ..partition import PartitionConfig

        if config is None:
            config = PartitionConfig(**kwargs)
        elif kwargs:
            raise ValueError(
                "with_partitioning: pass a PartitionConfig OR keyword "
                "arguments for one, not both")
        self._claim_strategy("with_partitioning")
        mesh = config.build_mesh(devices)
        if config.collectives_active():
            # bucketed / quantized DP gradient all-reduce: rewrite the
            # program (idempotent) BEFORE resolving shardings so the
            # resolve pass and the executor both see the final op list.
            # The bucket cap resolves against THIS mesh: a dp axis that
            # spans hosts picks the per-axis form's dcn bucket (bigger
            # buckets amortize DCN latency), an ICI-local one its dp
            # bucket
            from ..parallel.collectives import ensure_planned

            ensure_planned(
                self._program,
                bucket_mb=config.effective_bucket_mb(mesh),
                quantization=config.collective_quantization,
                quant_block=config.collective_quant_block)
        resolved = config.resolve(self._program, mesh=mesh)
        self._mesh = resolved.mesh
        self._in_shardings = dict(resolved.in_shardings)
        self._state_shardings = dict(resolved.state_shardings) or None
        self._partition = resolved
        return self

    @property
    def partition(self):
        """The ResolvedPartition attached by with_partitioning (None
        otherwise) — ``.report()`` answers "what sharded and why not
        the rest"."""
        return self._partition

    def with_sequence_parallel(self, sp: int, dp: int = 1,
                               places=None,
                               mode: str = "ring") -> "CompiledProgram":
        """Sequence (context) parallelism: shard dim 1 — the sequence
        axis of [B, S, ...] data vars — over an `sp` mesh axis,
        optionally combined with batch sharding over `dp`. The fused
        flash_attention op detects the sp axis at lowering time and
        runs one of two strategies (beyond the reference, SURVEY §5:
        it has no long-context parallelism):

          mode="ring"    — K/V shards rotate over ICI via ppermute
                           (parallel/ring_attention.py); works for any
                           head count, comm = sp-1 K/V rotations.
          mode="ulysses" — all-to-all head<->sequence re-sharding
                           (parallel/ulysses.py, the DeepSpeed-Ulysses
                           recipe); needs heads % sp == 0, comm = 2
                           activation all-to-alls.
        """
        from jax.sharding import PartitionSpec as P

        if mode not in ("ring", "ulysses"):
            raise ValueError(f"with_sequence_parallel: mode must be "
                             f"'ring' or 'ulysses', got {mode!r}")
        self._claim_strategy("with_sequence_parallel")
        self._axis_env = {"sp_mode": mode}
        self._mesh = self._axis_mesh("sp", sp, dp, places)
        shardings = {}
        for v in self._program.global_block().vars.values():
            if not (getattr(v, "is_data", False) and v.shape):
                continue
            lead = "dp" if dp > 1 else None
            # only dim 1 sizes divisible by sp are sequence-sharded; a
            # [B, 1] label or odd-sized side input stays replicated on
            # that dim instead of failing the jit sharding check
            if len(v.shape) >= 2 and v.shape[1] % sp == 0:
                shardings[v.name] = P(
                    *((lead, "sp") + (None,) * (len(v.shape) - 2)))
            elif lead:
                shardings[v.name] = P(
                    *((lead,) + (None,) * (len(v.shape) - 1)))
        self._in_shardings = shardings
        return self

    def with_expert_parallel(self, ep: int, dp: int = 1,
                             places=None,
                             dispatch: str = "psum") -> "CompiledProgram":
        """Expert parallelism: shard every switch_moe layer's expert
        weights (vars tagged _moe_expert_param) over an `ep` mesh axis,
        optionally combined with batch sharding over `dp`. The
        switch_moe op detects the ep axis at lowering time (ops/moe.py)
        and runs each device's local experts inside shard_map. Beyond
        the reference (SURVEY §2f: the snapshot has no MoE/EP).

          dispatch="psum"     — tokens replicated over ep; each rank
                                computes its experts for all tokens, a
                                psum combines. Simple; comm = one
                                activation psum.
          dispatch="alltoall" — the DeepSpeed/GShard form: tokens shard
                                over ep too; one all_to_all delivers
                                each rank exactly its experts' tokens,
                                a second returns outputs. Comm = 2x the
                                ROUTED tokens; dp*ep must divide the
                                batch size.
        """
        from jax.sharding import PartitionSpec as P

        if dispatch not in ("psum", "alltoall"):
            raise ValueError(f"with_expert_parallel: dispatch must be "
                             f"'psum' or 'alltoall', got {dispatch!r}")
        self._claim_strategy("with_expert_parallel")
        self._axis_env = {"ep_dispatch": dispatch}
        self._mesh = self._axis_mesh("ep", ep, dp, places)
        shardings = {}
        state_shardings = {}
        # alltoall shards the batch over BOTH axes; psum over dp only
        batch_axes = ((("dp", "ep") if dp > 1 else ("ep",))
                      if dispatch == "alltoall"
                      else (("dp",) if dp > 1 else None))
        expert_names = set()
        for v in self._program.global_block().vars.values():
            if getattr(v, "_moe_expert_param", False):
                state_shardings[v.name] = (
                    ("ep",) + (None,) * (len(v.shape) - 1))
                expert_names.add(v.name)
            elif getattr(v, "is_data", False) and v.shape and batch_axes:
                shardings[v.name] = P(
                    *((batch_axes,) + (None,) * (len(v.shape) - 1)))
        # expert params' optimizer accumulators (Adam moments etc.)
        # shard over ep too — the structural accumulator_owner tag, the
        # same mechanism ZeRO uses (parallel/sharding.py)
        for v in self._program.global_block().vars.values():
            if (getattr(v, "accumulator_owner", None) in expert_names
                    and v.shape and len(v.shape) >= 1 and v.shape
                    and max(v.shape) > 1):
                owner = self._program.global_block().var(
                    v.accumulator_owner)
                if tuple(v.shape) == tuple(owner.shape):
                    state_shardings[v.name] = (
                        ("ep",) + (None,) * (len(v.shape) - 1))
        if not state_shardings:
            raise ValueError(
                "with_expert_parallel: program has no switch_moe expert "
                "parameters (layers.switch_moe tags them)")
        self._in_shardings = shardings
        self._state_shardings = state_shardings
        return self

    def with_pipeline(self, places=None, dp: int = 1,
                      mp: int = 1) -> "CompiledProgram":
        """Attach a mesh whose `pp` axis is sized to the program's
        pipeline stages (PipelineOptimizer cut_list). The executor then
        compiles the step as the SPMD GPipe/1F1B schedule
        (core/pipeline_program.py).

        dp adds a data-parallel axis AROUND the pipeline: the schedule
        shard_maps manually over pp only, so dp stays GSPMD-auto
        inside each stage — batch sharding composes with the pipeline
        with zero manual collectives (forward data parallelism needs
        none; the dp gradient all-reduce happens in the outer jit,
        outside the stage dispatch). The reference composes these as
        separate systems (PipelineTrainer sections x NCCL rings,
        framework/trainer.h:118); here one mesh + one compiled
        executable carries both axes.

        mp (megatron tensor parallelism INSIDE a pipelined stage) is
        rejected here: auto-GSPMD collectives would land inside the
        schedule's device-varying lax.switch branches, whose
        full-mesh rendezvous deadlocks when other pp ranks are in
        other branches (observed on the dp2 x mp2 x pp2 CPU mesh).
        Tensor parallelism inside pipeline stages needs the manual
        path — parallel.pipeline.pipeline_train_step_3d, which takes
        explicit per-stage psums."""
        import jax
        from jax.sharding import Mesh, PartitionSpec as P
        import numpy as np

        if mp > 1:
            raise NotImplementedError(
                "with_pipeline(mp=...): tensor parallelism inside "
                "pipelined stages requires manual collectives — use "
                "parallel.pipeline.pipeline_train_step_3d, or compose "
                "with_pipeline(dp=...) with megatron sharding OUTSIDE "
                "a pipeline (plain pjit path)")
        cuts = getattr(self._program, "_pipeline_cuts", None)
        if not cuts:
            raise ValueError(
                "program has no pipeline cuts — minimize with "
                "PipelineOptimizer(cut_list=...) first"
            )
        if dp > 1:
            # data vars with a STATIC leading dim must divide over dp;
            # dynamic (-1) batch dims are validated against the actual
            # feed at dispatch-bind time (runtime/dispatch
            # validate_feed_shardings) — either way the failure is a
            # clear message here, not an opaque GSPMD/shard_map error
            for v in self._program.global_block().vars.values():
                if not (getattr(v, "is_data", False) and v.shape):
                    continue
                lead = v.shape[0]
                if lead is not None and lead > 0 and lead % dp:
                    raise ValueError(
                        f"with_pipeline(dp={dp}): data var {v.name!r} has "
                        f"leading (batch) dim {lead}, not divisible by "
                        f"dp={dp} — adjust the batch size or dp")
        self._claim_strategy("with_pipeline")
        n = len(cuts) + 1
        need = n * dp
        devs = places_to_devices(places) if places else jax.devices()
        if len(devs) < need:
            raise ValueError(
                f"pipeline needs pp*dp={need} devices, have {len(devs)}")
        if dp > 1:
            self._mesh = Mesh(
                np.array(devs[:need]).reshape(dp, n), ("dp", "pp"))
        else:
            self._mesh = Mesh(np.array(devs[:n]), ("pp",))
        self._in_shardings = {}
        if dp > 1:
            for v in self._program.global_block().vars.values():
                if getattr(v, "is_data", False) and v.shape:
                    self._in_shardings[v.name] = P(
                        *(("dp",) + (None,) * (len(v.shape) - 1)))
        return self

    def validate(self, fetch_list=None, strict: bool = False):
        """Run the static analyzer (paddle_tpu.analysis) over the
        wrapped program and return the AnalysisReport; with
        ``strict=True`` error-severity findings raise
        ProgramVerificationError. The same verification the executor
        performs pre-lowering under the ``validate_program`` flag,
        exposed here so build pipelines can lint a CompiledProgram
        before ever constructing an Executor."""
        from ..analysis import analyze_program, ProgramVerificationError

        fetch_names = [
            getattr(v, "name", str(v)) for v in (fetch_list or [])
        ]
        # a resolved mesh (with_partitioning / with_pipeline) gives the
        # PTL06x partition checks their axis sizes; unpartitioned
        # programs lint with mesh_axes=None (mesh checks stay quiet)
        mesh_axes = dict(self._mesh.shape) if self._mesh is not None else None
        report = analyze_program(
            self._program, fetch_names=fetch_names,
            label=f"CompiledProgram uid={self._program.uid}",
            mesh_axes=mesh_axes)
        if strict and not report.ok:
            raise ProgramVerificationError(report)
        return report

    # graph passthroughs used by reference code
    @property
    def program(self):
        return self._program


def places_to_devices(places):
    return [p.jax_device() for p in places]
