"""Run the index and serve paths once on a TPU and check every result
against a plain oracle.

    python chip_smoke.py [--seed N]             # phases A-C, one chip
    python chip_smoke.py --chips 4 [--seed N]   # phase F only, four chips

A. Deployment-size index: 4,000,000 unique int32 keys in one ΔTree arena
   (height 7, 151,024 ΔNodes, about 150 MB of device state) behind
   ``make_index("deltatree", engine="auto")``, which must resolve to the
   lockstep engine.  The arena is past the fused kernels' VMEM budget, so
   the XLA mirror walks it.  Search, a mixed update batch (three of them,
   to time the update program warm), search again, successor and range
   scans, against ``SetOracle`` and numpy.
B. Pallas phase: the same ops on an arena at the scan kernel's VMEM
   budget edge.  The compiled search and scan programs must hold a
   ``tpu_custom_call``; results, per-query hops and the updated arena must
   equal a scalar-engine index's bit for bit.
C. Serve: ``ServeScheduler`` on the granite smoke preset the serve
   benchmarks use, 8 requests x 16 new tokens through the compiled paged
   decode-attention kernel; every request must match a dense decode.
F. ``--chips 4`` only: the phase-A ops on a 4-shard DeltaForest, one
   shard per chip (each shard's arena inside the Pallas budget), against
   the single arena under the scalar engine and the oracle.

Every phase prints which walk and scan implementation ran, and the
seconds of each op's first call (compile included) and of a steady one.
The last line printed is the JSON verdict.  The script exits non-zero,
printing no verdict, on a machine without a TPU, when Pallas would run in
interpret mode, or when any check fails.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# the serving pager's map mode packs key and payload into int64: x64 is
# on for the whole process, before JAX is imported
os.environ["JAX_ENABLE_X64"] = "1"
sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import make_index  # noqa: E402
from repro.api.opbatch import OP_DELETE, OP_INSERT, OpBatch  # noqa: E402
from repro.core import deltatree as DT  # noqa: E402
from repro.core.oracle import SetOracle  # noqa: E402
from repro.kernels import ops as OPS  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402

N_KEYS = 4_000_000
MAX_DNODES = 151_024
HEIGHT = 7
N_QUERIES = 4096
N_UPDATES = 4096
KEY_HI = 1 << 30          # keys drawn from [1, KEY_HI); misses fill the rest
SCAN_ITEMS = 128
PALLAS_KEYS = 1_000_000


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok, msg) -> None:
    """A result check that still runs under ``python -O``."""
    if not ok:
        raise AssertionError(msg)


def timed(fn):
    """(outputs, seconds) of ``fn()``, outputs ready on the device."""
    t = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t


def first_and_steady(label: str, fn):
    """Run ``fn`` twice; print both times (the first includes any compile)."""
    out, first = timed(fn)
    out2, steady = timed(fn)
    log(f"{label}: first call {first!r} s, steady {steady!r} s")
    return out2


def require_tpu() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform {dev.platform!r})")
    if OPS.default_interpret():
        sys.exit("chip_smoke: REPRO_PALLAS_INTERPRET resolves to interpret "
                 "mode; unset it or set it to 0")


def require_kernel(label: str, lower) -> None:
    """Compile ``lower()`` (a ``jax.stages.Lowered``) and require a Pallas
    kernel in the program."""
    check("tpu_custom_call" in lower().compile().as_text(),
          f"{label}: no Pallas kernel in the compiled program")
    log(f"{label}: tpu_custom_call present")


def make_keys(rng, n: int) -> np.ndarray:
    draw = np.unique(rng.integers(1, KEY_HI, size=n + n // 8 + 1024,
                                  dtype=np.int32))
    return np.sort(rng.permutation(draw)[:n])


def impls(ix, tag: str) -> tuple[str, str]:
    """Print the walk and scan implementation the rule picks for one
    arena (per device, for a forest)."""
    t = ix.state.trees if ix.backend == "forest" else ix.state
    value, child = t.value, t.child
    if value.ndim == 3:   # forest: one shard per device
        value, child = value[0], child[0]
    q_tile = ix.cfg.tree.q_tile if ix.backend == "forest" else ix.cfg.q_tile
    kw = dict(height=HEIGHT, q_tile=q_tile or None)
    walk = OPS.walk_impl(value, child, **kw)
    scan = OPS.scan_impl(value, child, max_out=SCAN_ITEMS, **kw)
    log(f"{tag}: engine {ix.engine}, arena {value.shape[0]} ΔNodes, "
        f"walk {walk}, scan {scan}")
    return walk, scan


def run_ops(tag: str, ixs: dict, oracle: SetOracle, rng, *,
            same_structure: bool) -> dict:
    """The index ops of one phase, applied to every index in ``ixs``.
    The first index is checked against the oracle, the others against the
    first: results always, per-query hops where ``same_structure``."""
    names = list(ixs)

    def agree(what, outs, exact_hops=True):
        ref = outs[names[0]]
        for n in names[1:]:
            for i, (a, b) in enumerate(zip(ref, outs[n])):
                if i == 1 and not exact_hops:
                    continue
                check(np.array_equal(np.asarray(a), np.asarray(b)),
                      f"{tag} {what}: {n} differs from {names[0]} (out {i})")

    keys = oracle.keys()     # sorted live keys, refreshed after updates

    def search(what):
        hits = rng.choice(keys, N_QUERIES // 2)
        rand = rng.integers(1, KEY_HI, N_QUERIES // 2, dtype=np.int32)
        q = rng.permutation(np.concatenate([hits, rand])).astype(np.int32)
        jq = jnp.asarray(q)
        outs = {n: first_and_steady(f"{tag} {what} [{n}]",
                                    lambda ix=ix: ix.search(jq))
                for n, ix in ixs.items()}
        expect = oracle.snapshot_search(q)
        check(np.array_equal(np.asarray(outs[names[0]][0]), expect),
              f"{tag} {what}: found differs from the oracle")
        agree(what, outs, same_structure)
        log(f"{tag} {what}: {int(expect.sum())}/{len(q)} hits match the "
            "oracle")

    search("search")

    for i in range(3):   # three batches: the update program warm
        kinds = rng.choice([OP_INSERT, OP_DELETE], N_UPDATES).astype(np.int32)
        old = rng.choice(keys, N_UPDATES)
        new = rng.integers(1, KEY_HI, N_UPDATES, dtype=np.int32)
        ukeys = np.where(rng.random(N_UPDATES) < 0.5, old, new).astype(
            np.int32)
        batch = OpBatch.mixed(jnp.asarray(kinds), jnp.asarray(ukeys))
        res = {}
        for n in names:
            (ix, r), sec = timed(lambda ix=ixs[n]: ix.insert_delete(batch))
            ixs[n], res[n] = ix, np.asarray(r)
            log(f"{tag} update batch {i} [{n}]: {sec!r} s")
        expect = oracle.apply_updates(kinds, ukeys)
        keys = oracle.keys()
        check(np.array_equal(res[names[0]], expect),
              f"{tag} update {i}: results differ from the oracle")
        for n in names[1:]:
            check(np.array_equal(res[n], res[names[0]]),
                  f"{tag} update {i}: {n} results differ")
        check(not any(ix.alloc_failed() for ix in ixs.values()),
              f"{tag}: arena exhausted")
        log(f"{tag} update batch {i}: {int(expect.sum())}/{N_UPDATES} "
            "applied, match the oracle")
    if same_structure and len(names) > 1:
        for n in names[1:]:
            a, b = ixs[names[0]].state, ixs[n].state
            for plane in ("value", "mark", "child"):
                check(np.array_equal(np.asarray(getattr(a, plane)),
                                     np.asarray(getattr(b, plane))),
                      f"{tag}: {n} arena {plane} differs")
        log(f"{tag}: updated arenas identical across {names}")

    search("search after update")

    sq = rng.integers(0, KEY_HI, N_QUERIES, dtype=np.int32)
    jsq = jnp.asarray(sq)
    outs = {n: first_and_steady(f"{tag} successor [{n}]",
                                lambda ix=ix: ix.successor(jsq))
            for n, ix in ixs.items()}
    at = np.searchsorted(keys, sq, side="right")
    has = at < len(keys)
    found, succ = (np.asarray(x) for x in outs[names[0]])
    check(np.array_equal(found, has), f"{tag} successor: found differs")
    check(np.array_equal(succ[has], keys[at[has]]),
          f"{tag} successor: keys differ from np.searchsorted")
    agree("successor", outs)
    log(f"{tag} successor: {N_QUERIES} queries match np.searchsorted")

    density = len(keys) / KEY_HI
    for j, width in enumerate((40, 100, 400, 3000)):
        lo = int(rng.integers(1, KEY_HI))
        hi = lo + int(width / density)
        band = keys[(keys >= lo) & (keys <= hi)]
        for n, ix in ixs.items():
            r, sec = timed(lambda ix=ix: ix.range_scan(lo, hi,
                                                       max_items=SCAN_ITEMS))
            check(np.array_equal(r.keys, band[:SCAN_ITEMS]),
                  f"{tag} range_scan [{lo}, {hi}] [{n}]: keys differ")
            check(r.more == (len(band) > SCAN_ITEMS),
                  f"{tag} range_scan [{lo}, {hi}] [{n}]: more flag")
            log(f"{tag} range_scan {j} [{n}]: {len(r.keys)} of {len(band)} "
                f"keys in [{lo}, {hi}] match numpy, {sec!r} s")
    return ixs


def phase_index(seed: int) -> None:
    """A: the deployment-size arena under engine="auto"."""
    rng = np.random.default_rng(seed)
    keys = make_keys(rng, N_KEYS)
    ix, sec = timed(lambda: make_index(
        "deltatree", initial=keys, height=HEIGHT, max_dnodes=MAX_DNODES,
        engine="auto"))
    log(f"A build: {N_KEYS} keys, {sec!r} s (host bulk build + upload)")
    check(ix.engine == "lockstep", ix.engine)
    walk, scan = impls(ix, "A")
    check((walk, scan) == ("ref_delta_walk_fused", "ref_delta_scan_fused"),
          (walk, scan))
    run_ops("A", {"lockstep": ix}, SetOracle(keys), rng,
            same_structure=True)


def phase_pallas(seed: int) -> None:
    """B: an arena at the scan kernel's VMEM budget edge — Pallas walks
    and scans it — against the scalar engine on the same keys."""
    rng = np.random.default_rng(seed + 1)
    ub = 2 ** HEIGHT - 1
    m = OPS.fused_arena_cap((ub, ub, ub // 2 + 1),
                            OPS.default_q_tile(HEIGHT), SCAN_ITEMS)
    keys = make_keys(rng, PALLAS_KEYS)
    ixs = {e: make_index("deltatree", initial=keys, height=HEIGHT,
                         max_dnodes=m, engine=e)
           for e in ("lockstep", "scalar")}
    walk, scan = impls(ixs["lockstep"], "B")
    check((walk, scan) == ("veb_walk_fused", "veb_scan_fused"), (walk, scan))
    ix = ixs["lockstep"]
    q = jnp.asarray(keys[:N_QUERIES])
    require_kernel("B search program",
                   lambda: DT.search_jit.lower(ix.cfg, ix.state, q))
    lohi = jnp.asarray([1], jnp.int32)
    require_kernel("B scan program", lambda: DT.scan_jit.lower(
        ix.cfg, ix.state, lohi, lohi, SCAN_ITEMS))
    run_ops("B", ixs, SetOracle(keys), rng, same_structure=True)


def phase_serve(seed: int) -> None:
    """C: continuous-batching decode over the paged KV cache."""
    from repro.configs import get_smoke_config
    from repro.kernels.delta_paged_attention import _paged_decode_attention
    from repro.models.registry import api
    from repro.serve import SchedulerConfig, ServeScheduler
    from repro.serving import PagerConfig

    rng = np.random.default_rng(seed + 2)
    cfg = get_smoke_config("granite_8b")
    m = api(cfg)
    params = m.init_params(jax.random.PRNGKey(seed))
    # benchmarks/serve_trace.py's pager
    pc = PagerConfig(num_pages=1024, page_size=4, max_seqs=256,
                     max_blocks=64, tree_height=5, maintenance="deferred",
                     maint_high_water=8)
    n_req, max_new = 8, 16
    # f32 matmuls at full precision on both sides of the comparison, so
    # that the paged and the dense decode differ by summation order only
    with jax.default_matmul_precision("highest"):
        sch = ServeScheduler(cfg, params, pc, SchedulerConfig(max_live=8))
        pager = sch.pager.index
        walk = OPS.walk_impl(pager.state.value, pager.state.child,
                             height=pc.tree_height)
        log(f"C pager: engine {pager.engine}, walk {walk}")
        prompts = [rng.integers(1, cfg.vocab_size, size=int(n),
                                dtype=np.int32)
                   for n in rng.integers(4, 17, n_req)]
        t = time.perf_counter()
        sids = [sch.submit(p, max_new=max_new) for p in prompts]
        sch.drain()
        sec = time.perf_counter() - t
        check(all(sch.active[s].done for s in sids), "unfinished requests")
        steps = sch.metrics()["serve"]["steps"]
        log(f"C serve: {n_req} requests x {max_new} tokens in {sec!r} s "
            f"(compile + run), {steps} steps")
        dt = sch.k_pages.dtype
        pages = jax.ShapeDtypeStruct(sch.k_pages.shape[1:], dt)
        q = jax.ShapeDtypeStruct((n_req, cfg.num_heads, cfg.head_dim), dt)
        require_kernel("C paged_decode_attention",
                       lambda: _paged_decode_attention.lower(
                           q, pages, pages,
                           jax.ShapeDtypeStruct((n_req, pc.max_blocks),
                                                jnp.int32),
                           jax.ShapeDtypeStruct((n_req,), jnp.int32),
                           interpret=False))
        for s in sids:
            req = sch.active[s]
            caches = m.init_caches(1, 128)
            logits, caches = m.prefill(params, jnp.asarray(req.prompt)[None],
                                       caches)
            toks = [int(jnp.argmax(logits[0, -1]))]
            ln = len(req.prompt)
            while len(toks) < req.max_new:
                lg, caches = m.decode_step(
                    params, jnp.asarray([[toks[-1]]], jnp.int32), caches,
                    jnp.asarray([ln], jnp.int32))
                toks.append(int(jnp.argmax(lg[0, 0])))
                ln += 1
            check(req.out == toks, (s, req.out, toks))
    log(f"C serve: all {n_req} requests match the dense decode")


def phase_forest(seed: int) -> None:
    """F: the 4-shard forest, one shard per chip, against one arena."""
    from repro.distributed import router as R

    rng = np.random.default_rng(seed + 3)
    keys = make_keys(rng, N_KEYS)
    forest, sec = timed(lambda: make_index(
        "forest", initial=keys, num_shards=4, engine="lockstep",
        height=HEIGHT, max_dnodes=-(-MAX_DNODES // 4)))
    log(f"F build: forest of 4 shards, {sec!r} s")
    # the reference: one arena under the scalar engine (its programs
    # compile in seconds, where the lockstep mirror's scan takes minutes)
    tree = make_index("deltatree", initial=keys, height=HEIGHT,
                      max_dnodes=MAX_DNODES, engine="scalar")
    mesh = R.forest_mesh(4)
    check(len(mesh.devices.flat) == 4, mesh)

    def placed(ix):
        value = ix.state.trees.value
        shards = value.addressable_shards
        devs = {sh.device for sh in shards}
        check(len(value.sharding.device_set) == 4 and len(devs) == 4, devs)
        check(sorted(sh.index[0].start for sh in shards) == [0, 1, 2, 3],
              shards)
        return sorted(str(d) for d in devs)

    log(f"F placement: one shard per device on {placed(forest)}")
    impls(forest, "F forest")
    impls(tree, "F tree")
    ixs = run_ops("F", {"forest": forest, "tree": tree}, SetOracle(keys),
                  rng, same_structure=False)
    log(f"F placement after updates: {placed(ixs['forest'])}")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()
    require_tpu()
    enable_compile_cache()
    dev = jax.devices()[0]
    log(f"device: {dev.device_kind} x {len(jax.devices())}, "
        f"jax {jax.__version__}, seed {args.seed}")
    if args.chips == 4:
        check(len(jax.devices()) == 4, jax.devices())
        phases = [phase_forest]
    else:
        phases = [phase_index, phase_pallas, phase_serve]
    for phase in phases:
        t = time.perf_counter()
        phase(args.seed)
        log(f"{phase.__doc__.split(':')[0]} passed in "
            f"{time.perf_counter() - t!r} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
