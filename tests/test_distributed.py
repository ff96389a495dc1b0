"""Distributed machinery: sharding specs, dry-run cells (subprocess).

Multi-device tests run in a subprocess with forced host devices so the
main pytest process keeps the default 1-device view (per assignment).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import abstract_params
from repro.train import sharding as S

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


class TestParamSpecs:
    def test_dense_rules(self):
        params = abstract_params(get_config("qwen2.5-14b"))
        specs = S.param_specs(params)
        wq = specs["groups"]["slot0"]["attn"]["wq"]
        assert tuple(wq) == (None, "data", "model")
        assert tuple(specs["embed"]) == ("model", "data")

    def test_moe_vs_stacked_dense_disambiguation(self):
        """Stacked dense (L,D,F) w_gate must NOT get expert rules."""
        dense = abstract_params(get_config("phi3-mini-3.8b"))
        moe = abstract_params(get_config("qwen3-moe-235b-a22b"))
        d_spec = S.param_specs(dense)["groups"]["slot0"]["mlp"]["w_gate"]
        m_spec = S.param_specs(moe)["groups"]["slot0"]["mlp"]["w_gate"]
        assert tuple(d_spec) == (None, "data", "model")     # (L, D, F)
        assert tuple(m_spec)[1] == "model"                  # (L, E, D, F)

    def test_nondivisible_dims_dropped(self):
        """granite: 40 experts on tp=16 → hybrid (no expert sharding)."""
        import numpy as np
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((1, 1), ("data", "model"))
        params = abstract_params(get_config("granite-moe-3b-a800m"))
        # with tp=16 metadata: use explicit spec fn on shapes

        class FakeMesh:
            shape = {"data": 16, "model": 16}
            axis_names = ("data", "model")
        specs = S.param_specs(params, FakeMesh())
        wg = specs["groups"]["slot0"]["mlp"]["w_gate"]   # (L, 40, D, F)
        assert tuple(wg) == (None, None, "data", "model")

    def test_zero3_profile(self):
        class FakeMesh:
            shape = {"data": 16, "model": 16}
            axis_names = ("data", "model")
        params = abstract_params(get_config("h2o-danube-1.8b"))
        specs = S.param_specs(params, FakeMesh(), profile="zero3")
        flat = jax.tree.leaves(specs, is_leaf=lambda x: hasattr(x, "index")
                               or x is None)
        # every spec either replicates or shards over ALL axes combined
        for spec in jax.tree.leaves(
                specs, is_leaf=lambda s: s.__class__.__name__ ==
                "PartitionSpec"):
            for entry in spec:
                assert entry in (None, ("data", "model"))


@pytest.mark.slow
class TestDryRunSubprocess:
    def test_smoke_cell_lowering(self, tmp_path):
        """Lower+compile a smoke config on an 8-device fake mesh in a
        subprocess (keeps this process single-device)."""
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=8"
            import jax, json
            from repro.configs import smoke_config
            from repro.models import inputs as I
            from repro.models.config import ShapeConfig
            from repro.train import OptConfig, abstract_train_state, \
                sharding as S
            from repro.train.trainer import make_train_step

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 4), ("data", "model"))
            cfg = smoke_config("recurrentgemma-9b")
            shape = ShapeConfig("t", 32, 4, "train")
            specs = I.input_specs(cfg, shape)
            params, opt_state = abstract_train_state(cfg)
            p_sh = S.param_shardings(params, mesh)
            o_sh = {"m": p_sh, "v": p_sh,
                    "step": jax.sharding.NamedSharding(
                        mesh, jax.sharding.PartitionSpec())}
            b_sh = S.batch_shardings(specs, mesh)
            step = make_train_step(cfg, OptConfig(), mesh)
            with mesh:
                c = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                            donate_argnums=(0, 1)) \
                    .lower(params, opt_state, specs).compile()
            print(json.dumps({"ok": True,
                              "temp": c.memory_analysis()
                              .temp_size_in_bytes}))
        """)
        env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        assert rec["ok"] and rec["temp"] > 0


@pytest.mark.slow
class TestDistributedAnalytics:
    def test_sharded_analytics_match_single_device(self):
        """shard_map degree/SpMV/PageRank over 8 fake devices equal the
        single-device versions (the paper's analytics, mesh-parallel)."""
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp, numpy as np
            from repro.core.sparse import COO, spmv_t
            from repro.core import graph
            from repro.analytics import distributed as D

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((8,), ("data",))
            rng = np.random.default_rng(0)
            n, nnz = 200, 3000
            m = COO.from_numpy(rng.integers(0, n, nnz),
                               rng.integers(0, n, nnz),
                               rng.integers(1, 4, nnz).astype(np.float32),
                               (n, n))
            got = D.degree_sharded(m, mesh)
            exp = jax.ops.segment_sum(jnp.ones_like(m.vals), m.cols,
                                      num_segments=n)
            np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                                       rtol=1e-5)
            x = jnp.asarray(rng.normal(0, 1, n).astype(np.float32))
            np.testing.assert_allclose(
                np.asarray(D.spmv_t_sharded(m, x, mesh)),
                np.asarray(spmv_t(m, x)), rtol=1e-4, atol=1e-4)
            pr_d = D.pagerank_sharded(m, mesh, num_iters=15)
            pr_s = graph.pagerank(m, num_iters=15)
            np.testing.assert_allclose(np.asarray(pr_d), np.asarray(pr_s),
                                       rtol=1e-3, atol=1e-5)
            print("SHARDED_ANALYTICS_OK")
        """)
        env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "SHARDED_ANALYTICS_OK" in out.stdout


class TestPagerankProgram:
    def test_power_iteration_compiles_once(self):
        """The whole power iteration is one jitted program: a second job
        over the same adjacency shape reuses it instead of rebuilding
        and recompiling the shard_map bodies every iteration."""
        import jax.numpy as jnp
        from repro.analytics import distributed as D
        from repro.core.sparse import COO
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
        rng = np.random.default_rng(0)
        n, nnz = 97, 1001       # a shape no other test compiles
        m = COO.from_numpy(rng.integers(0, n, nnz), rng.integers(0, n, nnz),
                           np.ones(nnz, np.float32), (n, n))
        before = D._pagerank._cache_size()
        a = D.pagerank_sharded(m, mesh, num_iters=7)
        b = D.pagerank_sharded(m.astype(jnp.float32), mesh, num_iters=7)
        assert D._pagerank._cache_size() == before + 1
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(float(jnp.sum(a)), 1.0, rtol=1e-5)


class TestPodFsdp:
    def test_pod_fsdp_specs_span_pod_axis(self):
        class FakeMesh:
            shape = {"pod": 2, "data": 16, "model": 16}
            axis_names = ("pod", "data", "model")
        params = abstract_params(get_config("qwen2.5-14b"))
        specs = S.param_specs(params, FakeMesh(), profile="2d_podfsdp")
        wq = specs["groups"]["slot0"]["attn"]["wq"]      # (L, D, H·Dh)
        assert tuple(wq) == (None, ("pod", "data"), "model")
        # single-pod mesh: profile degrades gracefully to plain data-FSDP
        class SinglePod:
            shape = {"data": 16, "model": 16}
            axis_names = ("data", "model")
        specs1 = S.param_specs(params, SinglePod(), profile="2d_podfsdp")
        wq1 = specs1["groups"]["slot0"]["attn"]["wq"]
        assert tuple(wq1) == (None, "data", "model")


@pytest.mark.slow
class TestGradCompression:
    def test_int8_pod_mean_error_bounded(self):
        """int8 cross-pod mean: wire bytes 4× less than f32, error within
        the quantization bound (subprocess: 2-pod fake mesh)."""
        code = textwrap.dedent("""
            import os
            os.environ["XLA_FLAGS"] = \
                "--xla_force_host_platform_device_count=8"
            import jax, jax.numpy as jnp, numpy as np
            from jax.sharding import NamedSharding, PartitionSpec as P
            from repro.train.compression import compressed_pod_mean

            from repro.launch.mesh import make_mesh
            mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
            rng = np.random.default_rng(0)
            g = jnp.asarray(rng.normal(0, 0.1, (64, 32))
                            .astype(np.float32))
            grads = {"w": g, "b": jnp.asarray(
                rng.normal(0, 3.0, (16,)).astype(np.float32))}
            out = compressed_pod_mean(grads, mesh)
            # replicated inputs: exact mean == input; error ≤ scale/2
            for k in grads:
                scale = float(jnp.max(jnp.abs(grads[k]))) / 127.0
                err = float(jnp.max(jnp.abs(out[k] - grads[k])))
                assert err <= scale / 2 + 1e-7, (k, err, scale)
            print("COMPRESS_OK")
        """)
        env = dict(os.environ, PYTHONPATH=os.path.abspath(SRC))
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=600)
        assert out.returncode == 0, out.stderr[-2000:]
        assert "COMPRESS_OK" in out.stdout
