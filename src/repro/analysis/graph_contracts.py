"""The contract registry: build, lower and compile every jitted entrypoint.

Each entrypoint the repo's perf guarantees live in gets a builder that
constructs a smoke-sized instance (tiny shapes — the *structure* of the
optimized HLO is what the contracts assert, and XLA's rewrites are
shape-independent at this granularity) and returns its compiled HLO text.
``run_contract`` marries a builder to its :class:`GraphContract`.

Builders accept a ``mutant`` hook used by the mutation tests (and by
``tools/check_graphs.py --mutate`` to prove the gate bites):

* ``"restack"``       — re-stacks every class stack slice-by-slice after
  the update (exactly the PR-5 data movement the scanned engine removed);
* ``"host_transfer"`` — plants a ``jax.debug.print`` host callback;
* ``"f64"``           — routes the loss through an f64 round-trip (lowered
  under ``enable_x64`` so the promotion actually materializes);
* ``"no_donate"``     — drops buffer donation.

All lowering happens on CPU; contracts assert structure (ops, dtypes,
aliasing, trip counts) and trip-weighted costs, none of which need real
hardware.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .contracts import ContractResult, GraphContract, check_hlo

MUTANTS = ("restack", "host_transfer", "f64", "no_donate")


# --------------------------------------------------------------------------
# mutation hooks
# --------------------------------------------------------------------------

def _mutate_restack(tree):
    """Rebuild every (C, n, *member) class-stack leaf with a per-slice
    restack — the rank-(member+2) concatenate the scanned engine's
    class-keyed storage eliminated. Slices get distinct epsilon offsets so
    XLA cannot fold the concatenate back into a no-op copy."""
    def r(leaf):
        if getattr(leaf, "ndim", 0) >= 4 and jnp.issubdtype(leaf.dtype,
                                                            jnp.floating):
            parts = [leaf[:, i] + jnp.asarray(i * 1e-30, leaf.dtype)
                     for i in range(leaf.shape[1])]
            return jnp.stack(parts, axis=1)
        return leaf
    return jax.tree.map(r, tree)


def _mutate_f64(x):
    """f64 round-trip (a real one only under enable_x64)."""
    return jax.tree.map(
        lambda l: (l.astype(jnp.float64) * 2.0).astype(l.dtype) / 2.0
        if jnp.issubdtype(l.dtype, jnp.floating) else l, x)


@contextlib.contextmanager
def _lowering_ctx(mutant: Optional[str]):
    if mutant == "f64":
        with jax.enable_x64(True):
            yield
    else:
        yield


# --------------------------------------------------------------------------
# smoke fixtures
# --------------------------------------------------------------------------

def _quad_loss(params, batch, rng):
    return sum(jnp.sum(v ** 2) for _, v in sorted(params.items())), {}


def _train_setup(backend: str):
    """3-block wq/wo (two spec-split groups, one 2-member scan class) plus
    an odd singleton — the smallest instance exercising scan-over-classes,
    spec-aware grouping AND the fused flatten path."""
    from repro.core.device import DeviceConfig
    from repro.core.digital_opt import DigitalOptConfig, ScheduleConfig
    from repro.core.plan import AnalogPlan, TilePolicy
    from repro.core.tile import TileConfig
    from repro.core.trainer import AnalogTrainer, TrainerConfig

    dev = DeviceConfig(dw_min=0.01, sigma_pm=0.3, sigma_d2d=0.1,
                       sigma_c2c=0.05)
    extra = {"rng": "hash", "update_backend": "fused"} \
        if backend == "fused" else {}
    tile = TileConfig(algorithm="erider", device_p=dev, device_w=dev,
                      lr_p=0.5, lr_w=0.5, gamma=0.1, eta=0.1, chopper_p=0.1,
                      **extra)
    cfg = TrainerConfig(
        tile=tile,
        digital=DigitalOptConfig(kind="sgd"),
        schedule=ScheduleConfig(kind="constant", base_lr=0.1))
    tr = AnalogTrainer(
        _quad_loss, cfg,
        plan=AnalogPlan.of(("**", TilePolicy(tile, name="contract"))))
    params = {}
    for i in range(3):
        params[f"l{i}/attn/wq"] = 0.1 * jnp.ones((8, 8))
        params[f"l{i}/attn/wo"] = 0.1 * jnp.ones((8, 8))
    params["odd"] = 0.1 * jnp.ones((4, 24))
    state = tr.init(jax.random.PRNGKey(0), params)
    return tr, state


def _serve_setup():
    from repro.configs import get_config
    from repro.models.lm import LM
    from repro.serving import EngineConfig
    from repro.serving.sampling import FeedBuilder

    cfg = get_config("qwen2-0.5b", smoke=True)
    model = LM(cfg)
    params = model.init(jax.random.PRNGKey(42))
    ecfg = EngineConfig(lanes=4, page_size=8, num_pages=33, max_len=64)
    paged = model.init_paged_cache(ecfg.lanes, ecfg.num_pages,
                                  ecfg.page_size, ecfg.max_len)
    feed = FeedBuilder(cfg)(np.zeros((1, 16), np.int32))
    return model, params, ecfg, paged, feed


def _compile(fn, args, donate, mutant: Optional[str]) -> str:
    if mutant == "no_donate":
        donate = ()
    with _lowering_ctx(mutant):
        jfn = jax.jit(fn, donate_argnums=donate)
        return jfn.lower(*args).compile().as_text()


# --------------------------------------------------------------------------
# entrypoint builders: name -> optimized HLO text
# --------------------------------------------------------------------------

def _wrap_step(step, mutant: Optional[str]):
    """Apply a mutation inside a train_step-shaped fn(state, batch)."""
    if mutant == "restack":
        def mutated(state, batch):
            new_state, metrics = step(state, batch)
            bank = new_state["tiles"]
            from repro.core.tile import TileBank
            new_state["tiles"] = TileBank.from_classes(
                {c: _mutate_restack(arr)
                 for c, arr in bank.classes.items()},
                bank.index, bank.class_index, bank.policies)
            return new_state, metrics
        return mutated
    if mutant == "host_transfer":
        def mutated(state, batch):
            new_state, metrics = step(state, batch)
            jax.debug.print("contract-mutation loss={l}", l=metrics["loss"])
            return new_state, metrics
        return mutated
    if mutant == "f64":
        def mutated(state, batch):
            new_state, metrics = step(state, batch)
            metrics = dict(metrics, loss=_mutate_f64(metrics["loss"]))
            return new_state, metrics
        return mutated
    return step


def build_train_step_scanned(mutant: Optional[str] = None) -> str:
    tr, state = _train_setup("vmap")
    return _compile(_wrap_step(tr.train_step, mutant),
                    (state, jnp.zeros(())), (0,), mutant)


def build_train_step_fused(mutant: Optional[str] = None) -> str:
    tr, state = _train_setup("fused")
    return _compile(_wrap_step(tr.train_step, mutant),
                    (state, jnp.zeros(())), (0,), mutant)


def build_begin_step(mutant: Optional[str] = None) -> str:
    """Phase 1 alone (chopper draw / Q-tilde sync) over the donated bank —
    the graph `launch/train` warm-starts before the first full step."""
    from repro.core import algorithms as alg
    from repro.core.tile import TileBank
    from repro.core.trainer import _vmap_tile

    tr, state = _train_setup("vmap")
    bank = state["tiles"]

    def begin(bank: TileBank, key_raw):
        key = jax.random.wrap_key_data(key_raw)
        begun = tr._grouped_apply(
            bank,
            lambda gcfg: _vmap_tile(lambda ts, k: alg.begin_step(ts, k, gcfg)),
            key)
        out = TileBank.from_classes(begun, bank.index, bank.class_index,
                                    bank.policies)
        if mutant == "restack":
            out = TileBank.from_classes(
                {c: _mutate_restack(arr) for c, arr in out.classes.items()},
                out.index, out.class_index, out.policies)
        if mutant == "host_transfer":
            leaf = jax.tree_util.tree_leaves(out.classes)[0]
            jax.debug.print("contract-mutation {c}", c=leaf.sum())
        if mutant == "f64":
            out = TileBank.from_classes(
                {c: _mutate_f64(arr) for c, arr in out.classes.items()},
                out.index, out.class_index, out.policies)
        return out

    key_raw = jax.random.key_data(jax.random.PRNGKey(1))
    return _compile(begin, (bank, key_raw), (0,), mutant)


def build_prefill_commit(mutant: Optional[str] = None) -> str:
    model, params, ecfg, paged, feed = _serve_setup()
    from repro.serving.sampling import sample_greedy

    prompt_len, page_size = 16, ecfg.page_size

    def prefill_commit(params, feed, paged, row, lane):
        dense = model.init_cache(1, prompt_len)
        logits, dense = model.prefill(params, feed, dense)
        tok = sample_greedy(logits)
        if mutant == "host_transfer":
            jax.debug.print("contract-mutation {t}", t=tok.sum())
        if mutant == "f64":
            paged = _mutate_f64(paged)
        if mutant == "restack":
            paged = _mutate_restack(paged)
        out = model.commit_prefill(paged, dense, row, lane,
                                   prompt_len=prompt_len,
                                   page_size=page_size)
        return tok, out

    row = jnp.zeros((ecfg.table_width,), jnp.int32)
    return _compile(prefill_commit, (params, feed, paged, row, 0), (2,),
                    mutant)


def build_serve_step_lanes(mutant: Optional[str] = None) -> str:
    model, params, ecfg, paged, _ = _serve_setup()

    def step_fn(params, last, cache, table, pos, live):
        toks, cache = model.serve_step_lanes(params, last, cache, table, pos,
                                             live)
        if mutant == "host_transfer":
            jax.debug.print("contract-mutation {t}", t=toks.sum())
        if mutant == "f64":
            cache = _mutate_f64(cache)
        if mutant == "restack":
            cache = _mutate_restack(cache)
        return toks, cache, pos + 1

    last = jnp.zeros((ecfg.lanes, 1), jnp.int32)
    table = jnp.zeros((ecfg.lanes, ecfg.table_width), jnp.int32)
    pos = jnp.zeros((ecfg.lanes,), jnp.int32)
    live = jnp.ones((ecfg.lanes,), bool)
    return _compile(step_fn, (params, last, paged, table, pos, live), (2,),
                    mutant)


def build_serve_step_lanes_gdc(mutant: Optional[str] = None) -> str:
    """serve_step_lanes behind in-graph Global Drift Compensation: the
    chunked signature reductions (counted ``lax.scan`` loops — the
    trip-count rule prices them, not the trip-1 fallback), the per-matrix
    alpha division and the correction multiply lower into ONE module with
    the decode step."""
    model, params, ecfg, paged, _ = _serve_setup()
    from repro.core.paths import path_str
    from repro.lifetime import gdc as lgdc

    flat, _ = jax.tree_util.tree_flatten_with_path(params)
    # every matrix-shaped leaf gets calibrated (the serve path calibrates
    # exactly the analog leaves; the structure is identical)
    sig0 = {path_str(kp): 1.0 for kp, leaf in flat
            if getattr(leaf, "ndim", 0) >= 2}

    def step_fn(params, last, cache, table, pos, live):
        corrected = lgdc.correct_in_graph(params, sig0)
        toks, cache = model.serve_step_lanes(corrected, last, cache, table,
                                             pos, live)
        if mutant == "host_transfer":
            jax.debug.print("contract-mutation {t}", t=toks.sum())
        if mutant == "f64":
            cache = _mutate_f64(cache)
        if mutant == "restack":
            cache = _mutate_restack(cache)
        return toks, cache, pos + 1

    last = jnp.zeros((ecfg.lanes, 1), jnp.int32)
    table = jnp.zeros((ecfg.lanes, ecfg.table_width), jnp.int32)
    pos = jnp.zeros((ecfg.lanes,), jnp.int32)
    live = jnp.ones((ecfg.lanes,), bool)
    return _compile(step_fn, (params, last, paged, table, pos, live), (2,),
                    mutant)


def build_prefill_commit_batch(mutant: Optional[str] = None) -> str:
    """The PR-9 bucketed multi-lane prefill: 2 rows padded to a 16-token
    length bucket, masked in-graph, K/V scattered straight into the rows'
    pages, last valid position sampled in-graph."""
    model, params, ecfg, paged, _ = _serve_setup()
    from repro.serving.sampling import sample_greedy

    def prefill_batch(params, tokens, paged, tables, lanes, starts, lengths,
                      fresh):
        if mutant == "f64":
            paged = _mutate_f64(paged)
        if mutant == "restack":
            paged = _mutate_restack(paged)
        logits, out = model.prefill_commit_batch(
            params, tokens, paged, tables, lanes, starts, lengths, fresh)
        tok = sample_greedy(logits)
        if mutant == "host_transfer":
            jax.debug.print("contract-mutation {t}", t=tok.sum())
        return tok, out

    B, Cb = 2, 16
    tokens = jnp.zeros((B, Cb), jnp.int32)
    tables = jnp.zeros((B, ecfg.table_width), jnp.int32)
    lanes = jnp.arange(B, dtype=jnp.int32)
    starts = jnp.zeros((B,), jnp.int32)
    lengths = jnp.full((B,), Cb, jnp.int32)
    fresh = jnp.ones((B,), bool)
    return _compile(prefill_batch,
                    (params, tokens, paged, tables, lanes, starts, lengths,
                     fresh), (2,), mutant)


ENTRYPOINTS: Dict[str, Callable[[Optional[str]], str]] = {
    "train_step_scanned": build_train_step_scanned,
    "train_step_fused": build_train_step_fused,
    "begin_step": build_begin_step,
    "prefill_commit": build_prefill_commit,
    "serve_step_lanes": build_serve_step_lanes,
    "serve_step_lanes_gdc": build_serve_step_lanes_gdc,
    "prefill_commit_batch": build_prefill_commit_batch,
}


# --------------------------------------------------------------------------
# the contracts themselves
# --------------------------------------------------------------------------
# HBM ceilings are ~1.5x the measured smoke-instance cost (stable: the
# fixtures are deterministic); tightening them is free, loosening them
# trips the baseline diff. Collectives are zero on the single-device
# lowering by construction.

# u64: jax's partitionable threefry (the default since jax 0.5) builds its
# counter as a u64 iota that it splits into two u32 halves
# (``iota_2x32_shape`` in every jax.random.split). Those are a few index
# elements per split, not tensor math; the f64 guard stays.
_TRAIN_DTYPES = ("pred", "s32", "u32", "u64", "f32")
_SERVE_DTYPES = ("pred", "s32", "u32", "f32")

# copy ceiling note: the scan engines carry one layout copy of a class
# stack (f32[2,3,8,8] = 1536 B on the smoke fixture, lax.scan putting the
# scan axis first), so the train ceiling is 2048, one stack + slack —
# a second stack materializing (donation regression) trips hbm/donation.
# serving max_restacks=2 is the two RoPE rotate-half concatenates
# (rank 4, dims={3}); a cache restack adds more and trips.
CONTRACTS: Dict[str, GraphContract] = {
    "train_step_scanned": GraphContract(
        name="train_step_scanned",
        description="grouped engine, scan over same-structure classes: "
                    "zero per-step restacks of class stacks, donated state "
                    "round-trips in place",
        allowed_dtypes=_TRAIN_DTYPES,
        min_aliased=10,          # measured 26
        max_copy_bytes=2048,     # measured 1536 (scan-carry layout copy)
        max_hbm_bytes=1.5e6,     # measured 770k
    ),
    "train_step_fused": GraphContract(
        name="train_step_fused",
        description="fused batched pulse-update backend: one flattened "
                    "update per class, hash RNG (no threefry while-loops "
                    "beyond the scan), same zero-restack guarantee",
        allowed_dtypes=_TRAIN_DTYPES,
        min_aliased=10,          # measured 26
        max_copy_bytes=2048,     # measured 1536
        max_hbm_bytes=4.0e5,     # measured 192k (4x under the vmap path)
    ),
    "begin_step": GraphContract(
        name="begin_step",
        description="phase-1 chopper/Qt sync over the donated TileBank",
        allowed_dtypes=_TRAIN_DTYPES,
        min_aliased=10,          # measured 24
        max_copy_bytes=2048,     # measured 1536
        max_hbm_bytes=3.5e5,     # measured 170k
    ),
    "prefill_commit": GraphContract(
        name="prefill_commit",
        description="batch-1 dense prefill + in-graph first-token sample + "
                    "paged KV commit: donated page pools, no host sync "
                    "between sample and scatter",
        allowed_dtypes=_SERVE_DTYPES,
        max_restacks=2,          # RoPE rotate-half concats
        min_aliased=2,           # measured 2 (donated page pools)
        max_copy_bytes=196608,   # measured 131072 (embed-table copy)
        max_hbm_bytes=1.4e7,     # measured 7.0M
    ),
    "prefill_commit_batch": GraphContract(
        name="prefill_commit_batch",
        description="bucketed multi-lane masked prefill: donated page "
                    "pools, in-graph length masking and first-token "
                    "sampling, no dense-cache round trip",
        allowed_dtypes=_SERVE_DTYPES,
        max_restacks=2,          # RoPE rotate-half concats
        min_aliased=2,           # donated page pools
        max_copy_bytes=98304,    # measured 67584 (one KV pool)
        max_hbm_bytes=2.2e7,     # measured 15.2M
    ),
    "serve_step_lanes_gdc": GraphContract(
        name="serve_step_lanes_gdc",
        description="GDC-corrected decode step: chunked signature "
                    "reductions (counted scans — every while carries or "
                    "derives a trip count), in-graph alpha correction, "
                    "then the same donated-cache decode guarantees",
        allowed_dtypes=_SERVE_DTYPES,
        max_restacks=2,          # RoPE rotate-half concats
        min_aliased=2,           # donated page pools
        max_copy_bytes=98304,    # measured 67584 (same KV-pool copy)
        max_hbm_bytes=1.4e7,     # measured 9.3M (decode + signature sweep)
    ),
    "serve_step_lanes": GraphContract(
        name="serve_step_lanes",
        description="one decode step across all lanes at per-lane "
                    "positions: donated cache, zero host transfers "
                    "(a callback stalls every lane), f32-only math",
        allowed_dtypes=_SERVE_DTYPES,
        max_restacks=2,          # RoPE rotate-half concats
        min_aliased=2,           # measured 2
        max_copy_bytes=98304,    # measured 67584 (one KV pool)
        max_hbm_bytes=1.1e7,     # measured 5.4M
    ),
}

assert set(CONTRACTS) == set(ENTRYPOINTS)


def run_contract(name: str, mutant: Optional[str] = None) -> ContractResult:
    hlo = ENTRYPOINTS[name](mutant)
    return check_hlo(CONTRACTS[name], hlo)


def run_contracts(names: Optional[Iterable[str]] = None,
                  mutant: Optional[str] = None) -> List[ContractResult]:
    return [run_contract(n, mutant) for n in (names or sorted(CONTRACTS))]
