"""Launch layer: cell construction + lower/compile on a small host mesh,
the dry-run record schema (subprocess: needs >1 device), and where the
launcher keeps its persistent compile cache."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

PREAMBLE = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, "src")
import jax
"""


def run_sub(code: str, timeout: int = 420) -> str:
    r = subprocess.run([sys.executable, "-c",
                        PREAMBLE + textwrap.dedent(code)],
                       capture_output=True, text=True, cwd="/root/repo",
                       timeout=timeout)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr}"
    return r.stdout


def test_cells_compile_on_host_mesh():
    run_sub("""
        from repro.launch.specs import build_cell
        from repro.roofline.analysis import analyze_compiled
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        for arch, shape in (("gat-cora", "full_graph_sm"),
                            ("dcn-v2", "serve_p99"),
                            ("graphsage-reddit", "molecule")):
            cell = build_cell(arch, shape, mesh)
            with mesh:
                compiled = cell.lower().compile()
                rl, coll, memd = analyze_compiled(compiled, 8,
                                                  cell.model_flops)
            assert rl.step_s > 0 and memd["temp_bytes"] >= 0
            print("OK", arch, shape, rl.bottleneck)
    """)


def test_cell_grid_covers_assignment():
    from repro.configs import ARCH_IDS, cells, get_skips, shapes_for
    all_cells = cells(include_skipped=True)
    assert len(all_cells) == 40, len(all_cells)  # the assigned 40 cells
    skipped = [c for c in all_cells if c[2]]
    assert len(skipped) == 4                     # long_500k on 4 LM archs
    assert all(s == "long_500k" for _, s, _ in skipped)
    # gemma2 runs long_500k (hybrid attention)
    assert "long_500k" not in get_skips("gemma2-27b")


def test_production_mesh_shapes():
    run_sub("""
        # 8 host devices can't back the real 512 mesh; validate shapes via
        # the spec'd constructor logic without building it.
        from repro.launch import mesh as m
        import inspect
        src = inspect.getsource(m.make_production_mesh)
        assert "(2, 16, 16)" in src and "(16, 16)" in src
        assert '("pod", "data", "model")' in src
        print("OK")
    """)


def test_compile_cache_dir_fixed_unless_placed_from_outside(tmp_path):
    """``use_compile_cache`` keeps a directory set from outside and
    otherwise points at ``<checkout>/.jax_cache`` — one fixed path."""
    import jax

    from repro.launch.compile_cache import CHECKOUT, use_compile_cache
    assert os.path.isdir(os.path.join(CHECKOUT, "src", "repro"))
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        assert use_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == str(tmp_path)
        jax.config.update("jax_compilation_cache_dir", None)
        path = use_compile_cache()
        assert path == os.path.join(CHECKOUT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_launcher_writes_cache_where_env_places_it(tmp_path):
    """A launcher run with ``JAX_COMPILATION_CACHE_DIR`` set writes its
    compiled programs there."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"),
               PYTHONPATH=os.path.join(repo, "src"))
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.estimate", "--graph",
         "powerlaw:n=150,m=2000", "--motif", "M5-3", "--delta", "3000",
         "--k", "512", "--chunk", "256"],
        capture_output=True, text=True, cwd=repo, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    assert any(f.endswith("-cache") for f in os.listdir(tmp_path / "jc"))
