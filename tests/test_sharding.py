"""Sharding planner: tier selection, divisibility degradation (never errors),
head-padding functional equivalence, and spec construction on a real multi-device
mesh (subprocess with forced host device count)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, all_archs, get, with_padded_heads
from repro.core import qlinear as ql
from repro.models import model as M
from repro.models.quantize import pad_head_params, quantize_tree
from repro.sharding import planner

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


class FakeMesh:
    """Just enough Mesh for make_plan/_maybe (shape lookup)."""
    def __init__(self, **axes):
        self.shape = dict(axes)


class TestPlanTiers:
    def test_tiers_for_assigned_archs(self):
        mesh = FakeMesh(data=16, model=16)
        expect = {
            "deepseek-coder-33b": "tp_ffn",     # 56 q heads, 8 kv
            "gemma2-9b": "tp_kv_rep",           # 16 q, 8 kv
            "hubert-xlarge": "tp_full",         # 16 q, 16 kv
            "zamba2-1.2b": "tp_full",           # 32 q, 32 kv
            "starcoder2-7b": "tp_ffn",          # 36 q
            "nemotron-4-15b": "tp_kv_rep",      # 48 q, 8 kv
        }
        for arch, tier in expect.items():
            plan = planner.make_plan(get(arch), SHAPES["train_4k"], mesh)
            assert plan.tier == tier, (arch, plan.tier, tier)

    def test_moe_modes(self):
        mesh = FakeMesh(data=16, model=16)
        assert planner.make_plan(get("llama4-scout-17b-a16e"), SHAPES["train_4k"],
                                 mesh).moe_mode == "ep"          # 16 experts
        assert planner.make_plan(get("granite-moe-3b-a800m"), SHAPES["train_4k"],
                                 mesh).moe_mode == "expert_tp"   # 40 experts, dff 512

    def test_seq_shard_kv_for_serving_kinds(self):
        mesh = FakeMesh(data=16, model=16)
        cfg = get("gemma2-9b")
        assert planner.make_plan(cfg, SHAPES["decode_32k"], mesh).seq_shard_kv
        assert planner.make_plan(cfg, SHAPES["prefill_32k"], mesh).seq_shard_kv
        assert not planner.make_plan(cfg, SHAPES["train_4k"], mesh).seq_shard_kv

    def test_never_raises_for_any_cell(self):
        mesh = FakeMesh(pod=2, data=16, model=16)
        for arch in all_archs():
            for shape in SHAPES.values():
                planner.make_plan(get(arch), shape, mesh)   # must not raise


class TestHeadPadding:
    def test_padded_counts(self):
        assert with_padded_heads(get("deepseek-coder-33b"), 16).n_heads == 64
        assert with_padded_heads(get("starcoder2-7b"), 16).n_heads == 48
        assert with_padded_heads(get("llama4-scout-17b-a16e"), 16).n_heads == 48
        assert with_padded_heads(get("gemma2-9b"), 16).n_heads == 16    # unchanged

    def test_functional_equivalence(self, key):
        """Padded model with zero-padded wq columns / wo rows computes the SAME
        function — the exactness claim behind serving head padding."""
        cfg = get("starcoder2-7b", smoke=True)          # 4 heads smoke
        cfg_pad = with_padded_heads(cfg, 3)             # 4 -> 6 heads
        assert cfg_pad.n_heads == 6
        params = M.init_params(key, cfg)
        params_pad = pad_head_params(params, cfg, cfg_pad)
        toks = jax.random.randint(key, (2, 16), 0, cfg.vocab)
        logits, _ = M.apply(params, {"tokens": toks}, cfg, mode="train")
        logits_pad, _ = M.apply(params_pad, {"tokens": toks}, cfg_pad, mode="train")
        np.testing.assert_allclose(np.asarray(logits), np.asarray(logits_pad),
                                   atol=2e-2)

    def test_ssm_family_not_padded(self):
        cfg = get("mamba2-130m")
        assert with_padded_heads(cfg, 16) is cfg


class TestParamSpecs:
    def test_specs_on_8dev_mesh_subprocess(self):
        """Full spec construction + jit lowering of a smoke train step on a real
        (4, 2) mesh — the dry-run machinery end-to-end, at test scale."""
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
            import jax, numpy as np
            from jax.sharding import Mesh
            from repro.configs import get, SHAPES
            import dataclasses
            from repro.launch.dryrun import build_cell, default_quant
            from repro.sharding import hints

            mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))
            cfg = get("starcoder2-7b", smoke=True)
            shape = dataclasses.replace(SHAPES["train_4k"], seq_len=64, global_batch=8)
            step, args, in_sh, out_sh, donate, plan, extra = build_cell(
                cfg, shape, mesh, default_quant("train"))
            with mesh, hints.sharding_hints(dp_axes=plan.dp_axes,
                                            tp_axis=plan.tp_axis, mesh=mesh):
                compiled = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                                   donate_argnums=donate).lower(*args).compile()
            print("OK", compiled.memory_analysis().temp_size_in_bytes > 0)
        """)
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=600,
                           env={**os.environ, "PYTHONPATH": SRC})
        assert "OK" in r.stdout, r.stderr[-2000:]

    def test_param_shardings_cover_tree(self, key):
        mesh = FakeMesh(data=4, model=2)
        # NamedSharding needs a real mesh; use shape-only checks through _param_spec.
        cfg = get("gemma2-9b", smoke=True)
        sds = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
        plan = planner.make_plan(cfg, SHAPES["train_4k"], mesh)
        flat = jax.tree_util.tree_flatten_with_path(sds)[0]
        for path, leaf in flat:
            spec = planner._param_spec(planner._path_str(path), leaf.shape, cfg,
                                       plan, mesh)
            assert len(spec) == len(leaf.shape)
            # every mesh axis used at most once
            used = [a for s in spec if s is not None
                    for a in ((s,) if isinstance(s, str) else s)]
            assert len(used) == len(set(used)), (path, spec)


class TestQuantizedServingSpecs:
    """Serving plans for prepared integer trees (DESIGN.md §3.7): scale leaves
    follow their weight's model-axis split; non-dividing shapes degrade to
    replication, never error."""

    def _plan(self, model=2):
        mesh = FakeMesh(data=8 // model, model=model)
        cfg = get("starcoder2-7b", smoke=True)
        return cfg, planner.make_serve_plan(cfg, mesh), mesh

    def test_scale_leaves_follow_weight_model_axis(self):
        cfg, plan, mesh = self._plan()
        d, f = cfg.d_model, cfg.d_ff
        # column-parallel up: qw shards d_out over model, sw follows d_out
        assert planner._param_spec("blocks/0/mlp/up/qw", (d, f), cfg, plan,
                                   mesh)[-1] == "model"
        assert planner._param_spec("blocks/0/mlp/up/sw", (f,), cfg, plan,
                                   mesh)[-1] == "model"
        # row-parallel down: qw shards d_in, bcol follows d_in, sw (d_out) replicates
        assert planner._param_spec("blocks/0/mlp/down/qw", (f, d), cfg, plan,
                                   mesh)[-2] == "model"
        assert planner._param_spec("blocks/0/mlp/down/bcol", (f,), cfg, plan,
                                   mesh)[-1] == "model"
        assert planner._param_spec("blocks/0/mlp/down/sw", (d,), cfg, plan,
                                   mesh) == P(None)
        # qalpha (effective-alpha scalar leaf): always replicated
        assert planner._param_spec("blocks/0/mlp/down/qalpha", (), cfg, plan,
                                   mesh) == P()

    def test_int4_group_scales_follow_row_parallel_shard(self):
        cfg, plan, mesh = self._plan()
        # row-parallel W4: per-layer sw is (G, d_out); the group axis follows the
        # weight's d_in shard when tp divides G (whole groups per shard). Scanned
        # leaves carry a leading layer-stack dim: (n_blocks, G, d_out).
        spec = planner._param_spec("tail/0/mlp/down/sw", (4, cfg.d_model), cfg,
                                   plan, mesh)
        assert spec[-2] == "model" and spec[-1] is None
        spec = planner._param_spec("blocks/0/mlp/down/sw", (2, 4, cfg.d_model),
                                   cfg, plan, mesh)
        assert spec == P(None, "model", None)
        # ... and replicates when tp does not divide G (G=3 vs tp=2)
        spec = planner._param_spec("blocks/0/mlp/down/sw", (2, 3, cfg.d_model),
                                   cfg, plan, mesh)
        assert all(s is None for s in spec)

    def test_stacked_int8_row_parallel_sw_never_shards_layer_axis(self):
        """A scanned int8 sw is (n_blocks, d_out): its dim -2 is the layer-stack
        axis, not a group axis — sharding it would make XLA all-gather the whole
        stack outside the decode scan. Must replicate even when n_blocks divides
        tp."""
        cfg, plan, mesh = self._plan()
        spec = planner._param_spec("blocks/0/mlp/down/sw", (2, cfg.d_model), cfg,
                                   plan, mesh)
        assert all(s is None for s in spec)

    def test_prepared_tree_covered_and_degrades_to_replication(self):
        """Every leaf of a fully quantized tree gets a rank-matching spec with each
        mesh axis used at most once; a mesh nothing divides (model=7) yields pure
        replication — never an error (the planner's §3.4 contract, extended to
        quantization metadata)."""
        cfg = get("starcoder2-7b", smoke=True)
        qsds = jax.eval_shape(
            lambda: quantize_tree(M.init_params(jax.random.PRNGKey(0), cfg),
                                  ql.W8A8_INT8))
        for mesh, expect_replicated in ((FakeMesh(data=4, model=2), False),
                                        (FakeMesh(data=1, model=7), True)):
            plan = planner.make_serve_plan(cfg, mesh)
            n_model_sharded = 0
            for path, leaf in jax.tree_util.tree_flatten_with_path(qsds)[0]:
                spec = planner._param_spec(planner._path_str(path), leaf.shape,
                                           cfg, plan, mesh)
                assert len(spec) == len(leaf.shape)
                used = [a for s in spec if s is not None
                        for a in ((s,) if isinstance(s, str) else s)]
                assert len(used) == len(set(used)), (path, spec)
                n_model_sharded += "model" in used
                if expect_replicated:
                    assert all(s is None for s in spec), (path, spec)
            if not expect_replicated:
                assert n_model_sharded > 0

    def test_int8_kv_cache_scale_leaves_follow_codes(self):
        """cache_shardings: k_scale/v_scale carry the same (B→dp, T→model) split
        as the int8 codes they dequantize."""
        import numpy as _np
        mesh = jax.sharding.Mesh(
            _np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
        cfg = get("starcoder2-7b", smoke=True)
        plan = planner.make_serve_plan(cfg, mesh)
        assert plan.seq_shard_kv
        caches = jax.eval_shape(
            lambda: M.init_cache(cfg, 4, 32, dtype=jnp.float32, kv_int8=True))
        sh = planner.cache_shardings(caches, cfg, plan, mesh)
        blk = sh["blocks"][0]
        # stacked leaves: (n_blocks, B, T, ...) — B on dp, T on model
        assert blk["k"].spec[1] == ("data",) and blk["k"].spec[2] == "model"
        assert blk["k_scale"].spec[:3] == blk["k"].spec[:3]
        assert blk["v_scale"].spec[:3] == blk["v"].spec[:3]


class TestPagedPoolShardings:
    def test_kv_head_lanes_shard_in_whole_heads_subprocess(self):
        """The stored pool layout ((P, ps, Hkv·D) codes, (P, 1, L) scale rows,
        DESIGN.md §3.8) on a CPU mesh: ``planner.cache_shardings`` and
        ``hints.constrain_kv_pages`` put the lane axis on the model axis when
        Hkv divides it, each device holding whole kv heads, the same heads in
        the code and scale pools; a scale row padded past its heads, or an
        Hkv the model axis does not divide, keeps its lanes whole."""
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            os.environ["JAX_PLATFORMS"] = "cpu"
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.configs import get
            from repro.launch.mesh import make_debug_mesh
            from repro.models import model as M
            from repro.sharding import hints, planner

            cfg = get("starcoder2-7b", smoke=True)          # Hkv 2, D 16
            Hkv, D = cfg.n_kv_heads, cfg.head_dim
            assert Hkv == 2, Hkv

            def lane_slices(sh, shape):
                return {(s[-1].start or 0, s[-1].stop or shape[-1])
                        for s in sh.devices_indices_map(shape).values()}

            for (data, model), ps, split, scale_split in (
                    ((2, 2), 8, True, False),    # Hkv·ps = 16: padded row
                    ((2, 2), 64, True, True),    # Hkv·ps = 128: no padding
                    ((1, 4), 64, False, False)): # 4 does not divide Hkv 2
                mesh = make_debug_mesh(data, model)
                plan = planner.make_serve_plan(cfg, mesh)
                caches = jax.eval_shape(lambda: M.init_cache(
                    cfg, 2, 128, kv_int8=True, layout="paged", page_size=ps))
                sh = planner.cache_shardings(caches, cfg, plan, mesh)["blocks"][0]
                blk = caches["blocks"][0]
                tp = "model" if split else None
                dp = plan.dp_axes                # the page axis always divides
                assert sh["k_pages"].spec == P(None, dp, None, tp), sh["k_pages"]
                if split:     # whole heads: device lanes [h·D, (h+1)·D)
                    assert lane_slices(sh["k_pages"], blk["k_pages"].shape) == {
                        (h * D, (h + 1) * D) for h in range(Hkv)}
                want = P(None, dp, None, "model" if scale_split else None)
                assert sh["k_scale_pages"].spec == want, (ps, sh["k_scale_pages"])
                if scale_split:   # the same heads: lanes [h·ps, (h+1)·ps)
                    assert lane_slices(sh["k_scale_pages"],
                                       blk["k_scale_pages"].shape) == {
                        (h * ps, (h + 1) * ps) for h in range(Hkv)}

                # the in-step constraint pins what the planner stores
                def pin(x, head_lanes):
                    with hints.sharding_hints(dp_axes=plan.dp_axes,
                                              tp_axis=plan.tp_axis, mesh=mesh):
                        return hints.constrain_kv_pages(x, Hkv, head_lanes)
                for name, head_lanes in (("k_pages", D), ("k_scale_pages", ps)):
                    leaf = blk[name]
                    x = jnp.zeros(leaf.shape[1:], leaf.dtype)
                    got = jax.jit(lambda x: pin(x, head_lanes))(x).sharding
                    want = jax.sharding.NamedSharding(mesh, P(*sh[name].spec[1:]))
                    assert got.is_equivalent_to(want, x.ndim), (name, got.spec)
            print("POOL-SHARDING-OK")
        """)
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=600,
                           env={**os.environ, "PYTHONPATH": SRC})
        assert "POOL-SHARDING-OK" in r.stdout, r.stderr[-3000:]


class TestDebugMesh:
    def test_make_debug_mesh_raises_with_device_count_hint(self):
        """A short host must raise with the XLA_FLAGS hint (like
        make_production_mesh), not silently build a wrong-shaped mesh."""
        from repro.launch.mesh import make_debug_mesh
        with pytest.raises(RuntimeError,
                           match="xla_force_host_platform_device_count"):
            make_debug_mesh(64, 64)


class TestTp2TokenParity:
    def test_tp2_decode_matches_single_device_subprocess(self):
        """tp=2 host-mesh serving emits token-identical greedy output to
        single-device decode on a *pure-TP* (1, 2) mesh — the degenerate-dp
        layout the tp=2/tp=4 matrix of tests/test_sharded_serving.py (which runs
        on (4, 2)/(2, 4) meshes) does not cover. Two forced devices only, so
        this stays cheap under tier-1."""
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
            os.environ["JAX_PLATFORMS"] = "cpu"
            import dataclasses
            import jax, numpy as np
            from repro.configs import get
            from repro.core import qlinear as ql
            from repro.models import model as M
            from repro.serving import engine as E
            from repro.launch.mesh import make_debug_mesh

            cfg = dataclasses.replace(get("starcoder2-7b", smoke=True),
                                      dtype="float32")
            params = M.init_params(jax.random.PRNGKey(0), cfg)
            rng = np.random.default_rng(0)
            prompts = [rng.integers(1, cfg.vocab, size=l).astype(np.int32)
                       for l in (5, 9)]

            def serve(mesh):
                eng = E.ServeEngine(cfg, params, batch_size=2, max_len=32,
                                    quant=ql.W8A8_CROSSQUANT, path="fake",
                                    mesh=mesh)
                eng.submit([p.copy() for p in prompts], max_new=4)
                return {r.rid: r.out for r in eng.run()}

            base = serve(None)
            got = serve(make_debug_mesh(1, 2))
            assert got == base, (got, base)
            print("TP2-PARITY-OK")
        """)
        r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                           text=True, timeout=600,
                           env={**os.environ, "PYTHONPATH": SRC})
        assert "TP2-PARITY-OK" in r.stdout, r.stderr[-2000:]
