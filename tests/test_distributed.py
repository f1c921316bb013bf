"""Multi-device correctness via subprocesses (the main process must stay at
one device for the rest of the suite). Each case runs `python -c` with
XLA_FLAGS=--xla_force_host_platform_device_count=8."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, n_dev: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_sharded_lm_loss_matches_single_device():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import LMConfig, RecallConfig
        from repro.models import transformer as T
        from repro.distributed import mesh_utils
        from repro.distributed.mesh_utils import sharding_ctx
        from repro.launch.mesh import make_mesh

        cfg = LMConfig(n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
                       d_ff=64, vocab=64, d_head=8, dtype="float32")
        rc = RecallConfig(exit_interval=1, superficial_layers=1)
        params = T.lm_init(jax.random.PRNGKey(0), cfg, rc, embed_out=16)
        toks = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0, 64)
        labels = jnp.roll(toks, -1, 1)
        fw = dict(block_q=8, block_kv=8, chunk=8)
        ref = float(T.lm_loss(params, cfg, rc, toks, labels, **fw)[0])

        mesh = make_mesh((4, 2), ("data", "model"))
        rules = mesh_utils.lm_rules(False)
        p_sh = mesh_utils.make_shardings(T.lm_specs(cfg, rc, embed_out=16),
                                         mesh, rules,
                                         abstract_tree=jax.tree.map(
                                             lambda x: x, params))
        params_s = jax.tree.map(lambda x, s: jax.device_put(x, s), params, p_sh)
        with sharding_ctx(mesh, rules):
            got = float(jax.jit(lambda p, t, l: T.lm_loss(
                p, cfg, rc, t, l, **fw)[0])(params_s, toks, labels))
        assert abs(ref - got) < 1e-4, (ref, got)
        print("OK", ref, got)
    """)


def test_compressed_psum_close_to_exact():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.distributed.collectives import compressed_psum

        mesh = jax.make_mesh((8,), ("data",))
        g = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 0.1

        def exact(x):
            return jax.lax.psum(x, "data")

        def comp(x):
            s, err = compressed_psum({"g": x}, "data")
            return s["g"], err["g"]

        ex = shard_map(exact, mesh=mesh, in_specs=P("data"), out_specs=P("data"))(g)
        got, err = shard_map(comp, mesh=mesh, in_specs=P("data"),
                             out_specs=(P("data"), P("data")))(g)
        rel = float(jnp.max(jnp.abs(ex - got)) / jnp.max(jnp.abs(ex)))
        assert rel < 0.05, rel
        # error feedback residual = exactly the local quantization error
        assert float(jnp.max(jnp.abs(err))) < float(jnp.max(jnp.abs(g))) / 64
        print("OK rel", rel)
    """)


def test_flash_decode_seqparallel_matches_ref():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.collectives import flash_decode_seqparallel
        from repro.kernels.decode_attention.ref import decode_attention_reference

        mesh = jax.make_mesh((8,), ("seq",))
        B, S, H, KV, D = 2, 64, 4, 2, 16
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q = jax.random.normal(ks[0], (B, H, D))
        k = jax.random.normal(ks[1], (B, S, KV, D))
        v = jax.random.normal(ks[2], (B, S, KV, D))
        lengths = jnp.array([40, 64], jnp.int32)
        ref = decode_attention_reference(q, k, v, lengths)
        fn = flash_decode_seqparallel(mesh, "seq")
        got = fn(q, k, v, lengths)
        err = float(jnp.max(jnp.abs(ref - got)))
        assert err < 2e-5, err
        print("OK", err)
    """)


def test_elastic_restore_across_meshes():
    run_py("""
        import tempfile, jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint.checkpointer import Checkpointer
        from repro.distributed import mesh_utils
        from repro.distributed.elastic import elastic_restore

        tree = {"w": jnp.arange(64.0).reshape(8, 8)}
        specs = {"w": ("embed", "mlp")}
        with tempfile.TemporaryDirectory() as d:
            ck = Checkpointer(d)
            mesh_a = jax.make_mesh((4, 2), ("data", "model"))
            rules = mesh_utils.lm_rules(False)
            sh = mesh_utils.make_shardings(specs, mesh_a, rules)
            placed = jax.tree.map(lambda x, s: jax.device_put(x, s), tree, sh)
            ck.save(10, placed)
            # restore onto a *different* mesh shape (elastic shrink)
            mesh_b = jax.make_mesh((2, 2), ("data", "model"))
            restored, man = elastic_restore(ck, tree, mesh_b, rules, specs)
            np.testing.assert_array_equal(np.asarray(restored["w"]),
                                          np.asarray(tree["w"]))
            assert man["step"] == 10
            print("OK elastic")
    """)


def test_tiny_mesh_dryrun_cell():
    """End-to-end analyze_cell machinery on a 2x2 mesh with a smoke arch."""
    run_py("""
        import jax, jax.numpy as jnp
        from repro.configs.base import get_arch, smoke_variant
        from repro.launch.steps import build_step
        from repro.launch import hlo_analysis as H
        from repro.distributed.mesh_utils import sharding_ctx
        from repro.launch.mesh import make_mesh

        spec = smoke_variant(get_arch("qwen2-1.5b"))
        shape = spec.shapes[0]
        mesh = make_mesh((2, 2), ("data", "model"))
        bundle = build_step(spec, shape, mesh)
        jitted = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                         out_shardings=bundle.out_shardings,
                         donate_argnums=bundle.donate_argnums)
        with sharding_ctx(mesh, bundle.rules):
            compiled = jitted.lower(*bundle.abstract_args).compile()
        ma = compiled.memory_analysis()
        assert ma.temp_size_in_bytes > 0
        stats = H.parse_collectives(compiled.as_text(), 4)
        assert stats.total_wire_bytes > 0, stats
        print("OK dryrun", stats.counts)
    """, n_dev=4)
