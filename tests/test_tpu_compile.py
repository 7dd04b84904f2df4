"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode runs these kernels on the CPU but cannot see what the
chip's compiler refuses: per-lane gathers, untiled block shapes, 64-bit
scalars under ``JAX_ENABLE_X64``, or more VMEM than the chip has.  The
TPU compiler is installed without a chip, so each test compiles one
kernel for ``v5e:2x2``'s first device, from shapes alone, and checks that
the kernel reached the program as a ``tpu_custom_call``.  Each runs with
64-bit mode off and on: the serving pager runs the whole process with it
on.

The topology is described inside a fixture, never while a module is
imported, so that only the worker that runs this file loads the TPU
library.
"""

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import deltatree as DT
from repro.kernels import ops as OPS
from repro.kernels import veb_search as V
from repro.kernels.delta_paged_attention import _paged_decode_attention
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e device, with the persistent compilation cache off
    around the compiles (an entry written for a described chip cannot be
    read back without one)."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile_text(fn, one_chip, x64, *shapes):
    with jax.enable_x64(x64):
        args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
                for s, d in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()


X64 = pytest.mark.parametrize("x64", [False, True], ids=["x32", "x64"])


@X64
@pytest.mark.parametrize("height,q_tile", [(7, 256), (9, 1024)])
def test_veb_walk_fused_compiles_at_budget_edge(one_chip, x64, height,
                                                q_tile):
    ub = 2 ** height - 1
    cp = 2 ** (height - 1)
    m = OPS.fused_arena_cap((ub, cp), q_tile)
    ubp, cpp = V._round_up(ub, 128), V._round_up(cp, 128)
    k = 4 * q_tile
    fn = lambda v, c, r, q: V.veb_walk_fused(
        v, c, r, q, height=height, q_tile=q_tile,
        max_rounds=OPS.walk_round_cap(height, m), interpret=False)
    text = _compile_text(fn, one_chip, x64, ((m, ubp), jnp.int32),
                         ((m, cpp), jnp.int32), ((k,), jnp.int32),
                         ((k,), jnp.int32))
    assert "tpu_custom_call" in text


@X64
def test_veb_walk_rows_compiles(one_chip, x64):
    k = 4096
    fn = lambda r, c, q: V.veb_walk_rows(r, c, q, height=7, q_tile=256,
                                         interpret=False)
    text = _compile_text(fn, one_chip, x64, ((k, 128), jnp.int32),
                         ((k, 128), jnp.int32), ((k,), jnp.int32))
    assert "tpu_custom_call" in text


@X64
def test_veb_scan_fused_compiles_at_budget_edge(one_chip, x64):
    height, q_tile, max_out = 7, 256, 128
    widths = (127, 127, 64)
    m = OPS.fused_arena_cap(widths, q_tile, max_out)
    k = 4 * q_tile
    fn = lambda v, mk, c, r, a, b: V.veb_scan_fused(
        v, mk, c, r, a, b, height=height, max_out=max_out, q_tile=q_tile,
        max_rounds=OPS.scan_round_cap(height, m), interpret=False)
    text = _compile_text(fn, one_chip, x64, *([((m, 128), jnp.int32)] * 3),
                         *([((k,), jnp.int32)] * 3))
    assert "tpu_custom_call" in text


@X64
def test_paged_decode_attention_compiles_at_granite_geometry(one_chip, x64):
    """Granite-8B's published attention: 32 query / 8 kv heads, head_dim
    128, bf16 pages of 16 tokens."""
    b, qh, kvh, d, ps, n_pages, maxp = 8, 32, 8, 128, 16, 512, 32
    fn = lambda q, k, v, bt, sl: _paged_decode_attention(
        q, k, v, bt, sl, interpret=False)
    text = _compile_text(fn, one_chip, x64, ((b, qh, d), jnp.bfloat16),
                         ((n_pages, kvh, ps, d), jnp.bfloat16),
                         ((n_pages, kvh, ps, d), jnp.bfloat16),
                         ((b, maxp), jnp.int32), ((b,), jnp.int32))
    assert "tpu_custom_call" in text


def _loop_copies(text, m):
    """(computation, shape) of every ``copy``/``copy-start`` of an (m, ...)
    array in a while body, or in what a while body calls (its branches,
    nested loops, fusions), of an optimised HLO module's text."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{\s*$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None:
            cur.append(line)
    calls, bodies = {}, []
    for name, lines in comps.items():
        text_c = "\n".join(lines)
        calls[name] = set(re.findall(
            r"(?:body|condition|to_apply|calls|true_computation|"
            r"false_computation)=%?([\w.\-]+)", text_c))
        for group in re.findall(r"branch_computations=\{([^}]*)\}", text_c):
            calls[name].update(x.strip().lstrip("%") for x in group.split(","))
        bodies += re.findall(r"body=%?([\w.\-]+)", text_c)
    inside, todo = set(), bodies
    while todo:
        name = todo.pop()
        if name in comps and name not in inside:
            inside.add(name)
            todo += list(calls[name])
    copy = re.compile(r"=\s*\(?(\w+\[%d(?:,\d+)*\])[^=]*?\b(?:copy|copy-start)\("
                      % m)
    return [(name, hit.group(1)) for name in sorted(inside)
            for line in comps[name] if (hit := copy.search(line))]


@X64
def test_expand_pass_updates_the_arena_in_place(one_chip, x64):
    """``_process_ins`` at 4,096 ΔNodes: no arena plane is copied inside
    its loop or the loop's branches.  A pass whose per-item branches each
    return the whole tree copies every plane they carry on every slot
    (34 such copies, 11.5 MB an iteration, at this size)."""
    m = 4096
    cfg = DT.TreeConfig(height=7, max_dnodes=m,
                        payload_bits=32 if x64 else 0)
    with jax.enable_x64(x64):
        tree = [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
                for x in DT.empty_layout(cfg)]
        dn = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
        text = jax.jit(lambda t, d: DT._process_ins(cfg, t, d)).lower(
            DT.DeltaTree(*tree), dn).compile().as_text()
    assert "while" in text
    assert _loop_copies(text, m) == []


@pytest.mark.parametrize("env_set", [True, False], ids=["env", "repo"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_set):
    """A set JAX_COMPILATION_CACHE_DIR leaves JAX's config alone; unset,
    the cache goes to <repo>/.jax_cache."""
    was = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert enable_compile_cache() == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir is None
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            repo = Path(__file__).resolve().parents[1]
            assert REPO_CACHE_DIR == repo / ".jax_cache"
            assert enable_compile_cache() == str(repo / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == str(
                repo / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
