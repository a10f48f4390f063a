"""Continuous-batching serve engine v2 over the quantized cache.

vLLM-style slot engine, rebuilt so the host only touches the device at
admission boundaries:

* **Batched prefill** — the scheduler hands over up to ``slots`` queued
  requests at once; they are right-padded to a length bucket and prefilled
  in one compiled call (per-row ``lengths`` keep the cache and logits exact;
  see ``models.prefill``). Architectures with recurrent blocks, where
  padding would corrupt the scan state, admit exact-length groups instead.
* **On-device decode loop** — sampling (greedy / temperature / top-k),
  per-slot EOS + max-token tracking, and the generated-token buffers all
  live in the device state pytree; ``lax.while_loop`` runs up to
  ``decode_block`` steps per compiled call and stops early once every slot
  is inactive. No ``int(...)`` / ``np.asarray`` per token — the host syncs
  once per chunk to harvest finished slots and admit new work.
* **Paged KV cache** (``kv_layout="paged"``) — instead of reserving a dense
  ``cache_len`` stripe per slot, attention layers share one global pool of
  fixed-size quantized blocks addressed through a per-slot block table
  (``serve.block_alloc`` owns the refcounted pool on the host). Admission
  switches from "fits in cache_len" to "enough free blocks", blocks are
  allocated lazily as decode crosses block boundaries, and harvest returns
  them to the pool — so capacity tracks actual token residency, not the
  worst-case request. Prompts longer than ``prefill_chunk`` are admitted as
  a sequence of fixed-size **chunked prefill** calls that append blocks
  incrementally (``models.prefill_tail``), removing the cache_len bound on
  prompt length.
* **Prefix sharing** (``prefix_cache=True``, paged only) — full blocks of
  written tokens are content-addressed in the allocator's rolling-hash
  index; a request whose prompt extends a cached prefix maps those pool
  blocks into its table (refcount++) and prefills **only the uncached
  tail** (``models.prefill_tail`` starting at the cached offset). The
  *split block* — the partial block where two prompts diverge — is shared
  too and cloned device-side on first write (copy-on-write,
  ``kernels.kvq_attn.ops.copy_pool_blocks``). Shared-prompt workloads
  (system-prompted chat, few-shot eval, best-of-n) drop from O(prompt) to
  O(tail) prefill per request.
* **Batched tail prefill** — up to ``tail_batch`` tail/chunked prefills
  are in flight at once, and every engine step advances ALL of them by
  one window in a single compiled tail-wave (per-row ``(c0, tail_len)``
  offsets, pad-masked like the cold wave), so a burst of prefix-hit
  arrivals no longer serializes one tail per step — warm TTFT under
  concurrency matches the cold batched wave. ``prefix_affinity`` orders
  the queue so requests sharing a cached chain admit back-to-back while
  the chain is hot in the allocator's LRU.
* **Preemption / swap-out** (``admission="optimistic"``) — instead of
  debiting a request's worst-case block count at admission, only its
  prompt footprint is allocated; when the pool later runs dry the engine
  picks a victim (``preempt="last_admitted"`` or ``"longest_remaining"``),
  swaps its quantized blocks to a host buffer (int8 payloads move 4x
  cheaper than fp32), requeues it, and restores it bit-exactly once the
  pool recovers — decode resumes mid-stream with identical tokens.
* **Scheduler** (``serve.scheduler``) — pluggable FCFS / shortest-prompt /
  EDF policies plus per-request TTFT/latency accounting; paged admission
  uses its head-of-line ``admit_ok`` hook so big requests aren't starved,
  and its ``pick_victim`` hook chooses preemption victims.
* **Streaming + SLO-aware admission** — a request may carry an
  ``on_tokens`` callback: freshly decoded spans drain incrementally from
  ``_harvest`` at decode-chunk / spec-wave granularity (and at swap-out)
  instead of only at finish. Requests may also carry a first-token
  ``deadline_ms`` and a ``priority`` class: ``sched_policy="edf"``
  admits earliest-deadline-first within priority, and ``slo_shed``
  (``"reject"`` / ``"downgrade"``) drops or demotes queued requests
  whose predicted TTFT — fitted from this engine's measured prefill and
  decode rates — already misses their deadline. ``serve.frontend``
  builds the asyncio host loop and the HTTP endpoint on these hooks.

All per-slot cache state (int8 KV / recurrent) stays in one pytree so the
decode chunk is a single compiled program regardless of slot occupancy;
inactive slots ride along masked (their commits are dropped — in paged mode
by parking their block-table rows on the out-of-range sentinel) and are
recycled by the next admission.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ATTENTION_BLOCKS, BLOCK_ATTN, ModelConfig
from repro.core.precision import parse_policy
from repro.core.qat import (attach_w4a8_exports, attach_w4a8_ref_planes,
                            make_ctx, w4a8_use_pallas, w4a8_weight_bytes)
from repro.kernels.kvq_attn.ops import copy_pool_blocks
from repro.models import (decode_step, init_cache, prefill, prefill_tail,
                          spec_verify)
from repro.obs.metrics import ServeMetrics
from repro.obs.trace import NULL_TRACER, Tracer
from repro.runtime.sharding import (param_shardings, serve_cache_shardings,
                                    serve_state_shardings)
from repro.serve.block_alloc import BlockAllocator, PoolDry
from repro.serve.sampling import (TOP_K_CAP, fold_step, sample_tokens,
                                  token_probs)
from repro.serve.scheduler import (PREEMPT_POLICIES, SHED_MODES, Scheduler)
from repro.serve.spec import (SpecConfig, accept_exact, accept_rejection,
                              make_draft)

_POOL_KEYS = ("k_q", "v_q", "s_k", "s_v")   # pool-shaped paged cache leaves


def _pow2_ceil(n: int) -> int:
    """Smallest power of two >= max(n, 1) — used to bucket dynamic batch
    dimensions so compile variants stay logarithmic."""
    p = 1
    while p < n:
        p *= 2
    return p


def _clamp_lengths(segments, lens):
    """Re-clamp every attention layer's per-slot ``length`` leaf to
    ``lens`` — the device half of speculative rollback (the draft cache
    before drafting, the target cache after acceptance)."""
    def clamp(path, leaf):
        if getattr(path[-1], "key", None) == "length":
            return jnp.broadcast_to(lens[None], leaf.shape)
        return leaf
    return [jax.tree_util.tree_map_with_path(clamp, seg)
            for seg in segments]


def _jsonable(x):
    """Recursively cast numpy/jax scalars and arrays to native Python
    types. ``stats()`` is an HTTP boundary (``/v1/stats``,
    ``/v1/metrics``): a stray ``np.int64`` deep in the dict is invisible
    until ``json.dumps`` raises in the server."""
    if isinstance(x, dict):
        return {k: _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, np.bool_):
        return bool(x)
    if isinstance(x, np.integer):
        return int(x)
    if isinstance(x, np.floating):
        return float(x)
    if isinstance(x, (np.ndarray, jax.Array)):
        return _jsonable(x.tolist())
    return x


# decode_block="auto" probe results, memoized per process so benchmark
# scripts constructing several engines don't re-pay the probe compiles
_PROBE_CACHE: Dict[tuple, int] = {}


def _device_local_bytes(tree) -> int:
    """One device's share of a pytree: sharded leaves count their shard
    bytes, replicated / single-device leaves their full size."""
    total = 0
    for leaf in jax.tree.leaves(tree):
        sh = getattr(leaf, "sharding", None)
        if sh is not None and hasattr(sh, "shard_shape"):
            total += (int(np.prod(sh.shard_shape(leaf.shape)))
                      * leaf.dtype.itemsize)
        else:
            total += getattr(leaf, "nbytes", 0)
    return total


def _arg_signature(args) -> str:
    """Compact shape signature of one wave call — built only when the
    call triggered a fresh compile, so it may walk the pytrees freely.
    Scalars (the static argnums ride along positionally) print verbatim,
    single arrays as dtype[shape], larger pytrees as a leaf-count digest:
    the varying axes that cause retraces live in the top-level arrays."""
    parts = []
    for a in args:
        if a is None or isinstance(a, (bool, int, float, str)):
            parts.append(repr(a))
            continue
        leaves = jax.tree.leaves(a)
        if len(leaves) == 1 and hasattr(leaves[0], "shape"):
            leaf = leaves[0]
            parts.append(f"{leaf.dtype}{list(leaf.shape)}")
        else:
            parts.append(f"tree#{len(leaves)}")
    return "(" + ", ".join(parts) + ")"


@dataclass(eq=False)                    # identity equality: the ndarray
class Request:                          # prompt field breaks value __eq__
    uid: int
    prompt: np.ndarray                  # (S,) int32
    max_new_tokens: int = 32
    eos_id: int = -1                    # -1: never stops early
    temperature: float = 0.0            # <= 0: greedy
    top_k: int = 0                      # 0: no top-k filtering
    seed: int = 0
    # --- SLO class (scheduler policy "edf" + engine slo_shed) ---
    deadline_ms: Optional[float] = None  # first-token SLO, from submit
    priority: int = 0                    # lower = more urgent (EDF class)
    # --- streaming ---
    # called as on_tokens(req, new_tokens, done) with each freshly
    # decoded span (decode_block / spec-wave granularity) instead of only
    # at finish; may fire from whatever thread steps the engine
    on_tokens: Optional[Callable] = None
    generated: List[int] = field(default_factory=list)
    done: bool = False
    shed: bool = False                  # rejected by SLO admission control
    _arrival: int = 0                   # set by the scheduler
    _streamed: int = 0                  # tokens already sent to on_tokens


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, *, policy: str = "A8d-C8-W4",
                 slots: int = 8, cache_len: int = 512,
                 max_new_cap: int = 256,
                 decode_block: Union[int, str] = 8,
                 sched_policy: str = "fcfs", prefill_bucket: int = 16,
                 kv_layout: str = "dense", block_size: int = 64,
                 num_blocks: Optional[int] = None,
                 max_seq_len: Optional[int] = None,
                 prefill_chunk: Optional[int] = None,
                 table_len: Optional[int] = None,
                 prefix_cache: bool = True,
                 admission: str = "reserve",
                 preempt: str = "last_admitted",
                 tail_batch: int = 0,
                 prefix_affinity: bool = True,
                 slo_shed: str = "none",
                 spec: Optional[SpecConfig] = None,
                 weights_layout: str = "bf16",
                 w4a8_backend: str = "auto",
                 trace: Optional[Tracer] = None,
                 mesh: Optional[Mesh] = None):
        self.cfg = cfg
        # observability rides on the engine from construction: the tracer
        # (a disabled NULL_TRACER unless the caller wants a trace — spans
        # still measure, nothing is recorded) and the pushed-histogram
        # half of the /v1/metrics surface
        self.trace = trace if trace is not None else NULL_TRACER
        self.metrics = ServeMetrics()
        self.mesh = mesh
        self.tp = 1
        if mesh is not None:
            if "model" not in mesh.axis_names:
                raise ValueError(
                    "serving mesh needs a 'model' axis for tensor "
                    f"parallelism; got axes {tuple(mesh.axis_names)}")
            self.tp = int(mesh.shape["model"])
        if weights_layout not in ("bf16", "w4a8"):
            raise ValueError(f"weights_layout must be 'bf16' or 'w4a8', "
                             f"got {weights_layout!r}")
        self.weights_layout = weights_layout
        self._w4a8_bytes = {"packed": 0, "replaced": 0}
        if weights_layout == "w4a8":
            pol = parse_policy(policy)
            # the packed path is real integer arithmetic at int8 activations
            # x int4 weights; a policy trained differently would serve
            # numerics it never saw
            if not (pol.enabled and pol.act_bits == 8 and pol.act_dynamic
                    and pol.weight_bits <= 4):
                raise ValueError(
                    "weights_layout='w4a8' needs a dynamic-A8 W4 policy "
                    f"(e.g. 'A8d-C8-W4'); got {policy!r}")
            params = attach_w4a8_exports(params, pol)
            self._w4a8_bytes = w4a8_weight_bytes(params)
        # activation hints only when every head count divides the TP axis;
        # otherwise the params already fell back to replication and a hint
        # would fight GSPMD's propagation
        attn_mode = "tp" if (self.tp > 1
                             and cfg.n_heads % self.tp == 0
                             and cfg.n_kv_heads % self.tp == 0) else ""
        self.ctx = make_ctx(policy, weights_layout=weights_layout,
                            w4a8_backend=w4a8_backend,
                            attn_shard_mode=attn_mode, mesh=mesh)
        if weights_layout == "w4a8" and not w4a8_use_pallas(self.ctx):
            # XLA:CPU can't fuse the nibble unpack into its gemm the way the
            # Pallas kernel does in-registers; cache the unpacked int8 plane
            # once so ref decode steps don't re-materialize it (results stay
            # bit-identical — same integer gemm)
            params = attach_w4a8_ref_planes(params)
        if mesh is not None:
            # commit the full weight tree (packed planes included) to the
            # mesh: column/row-parallel linears split over "model", so the
            # draft built below slices already-sharded leaves
            params = jax.device_put(
                params, param_shardings(cfg, mesh, params))
        self.params = params
        self.slots = slots
        self.cache_len = cache_len
        self.max_new_cap = max_new_cap
        self.prefill_bucket = prefill_bucket
        self.scheduler = Scheduler(sched_policy, trace=self.trace)
        # right-padded batched prefill is exact only when every block is
        # attention (causality isolates real tokens from padding); recurrent
        # scans absorb pad steps into their state, so those admit
        # exact-length groups instead.
        self._pad_ok = (all(k in ATTENTION_BLOCKS for k in cfg.block_pattern)
                        and not cfg.is_encdec)
        # full (non-sliding) attention caches are a hard capacity bound;
        # ring-buffered / recurrent state is not
        self._cache_bound = (BLOCK_ATTN in cfg.block_pattern
                             and not cfg.sliding_window)
        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', "
                             f"got {kv_layout!r}")
        self._paged = kv_layout == "paged"
        if self._paged:
            if (cfg.is_encdec or cfg.sliding_window
                    or any(k != BLOCK_ATTN for k in cfg.block_pattern)):
                raise ValueError(
                    "kv_layout='paged' requires a full-attention decoder "
                    "(no sliding window / recurrence / cross-attention); "
                    f"{cfg.name!r} has block pattern {cfg.block_pattern}")
            self.block_size = block_size
            # default pool = the dense engine's total reservation, so the
            # two layouts are comparable at equal HBM
            self.num_blocks = num_blocks or max(
                1, slots * cache_len // block_size)
            # default per-request cap matches the dense stripe: the table
            # width bounds how many keys each decode step walks, so leaving
            # it at the whole pool would cost slots-times the attention
            # work of the dense layout
            self.max_seq_len = max_seq_len or min(
                cache_len, self.num_blocks * block_size)
            self.table_len = table_len or -(-self.max_seq_len // block_size)
            self.prefill_chunk = prefill_chunk or 4 * prefill_bucket
            if admission not in ("reserve", "optimistic"):
                raise ValueError(f"admission must be 'reserve' or "
                                 f"'optimistic', got {admission!r}")
            if preempt not in PREEMPT_POLICIES:
                raise ValueError(f"preempt must be one of "
                                 f"{PREEMPT_POLICIES}, got {preempt!r}")
            # tail_batch caps how many tail/chunked prefills ride one
            # batched wave; 0 = every slot, 1 = the serialized legacy path
            if not 0 <= tail_batch <= slots:
                raise ValueError(f"tail_batch must be in [0, slots={slots}]"
                                 f", got {tail_batch}")
            self.tail_batch = tail_batch or slots
        self.prefix_cache = prefix_cache and self._paged
        self.prefix_affinity = prefix_affinity and self.prefix_cache
        self.admission = admission
        self.preempt = preempt
        if slo_shed not in SHED_MODES:
            raise ValueError(f"slo_shed must be one of {SHED_MODES}, "
                             f"got {slo_shed!r}")
        self.slo_shed = slo_shed
        self.spec = None
        if spec is not None:
            if not self._paged:
                raise ValueError("speculative decoding requires "
                                 "kv_layout='paged' (the rollback path is "
                                 "the paged allocator's trim)")
            self.spec = spec if isinstance(spec, SpecConfig) \
                else SpecConfig(**spec)
            # the draft slices the (already export-attached) target tree, so
            # under w4a8 it serves the same packed weights; a draft_policy
            # override only retunes its activation/cache bits
            self.draft_cfg, self.draft_params = make_draft(cfg, params,
                                                           self.spec)
            self.draft_ctx = make_ctx(self.spec.draft_policy or policy,
                                      weights_layout=weights_layout,
                                      w4a8_backend=w4a8_backend,
                                      attn_shard_mode=attn_mode, mesh=mesh)
            # the draft over-commits up to k positions past the accepted
            # extent before rollback; its dense ring must never wrap
            # into live history
            self._draft_cache_len = self.max_seq_len + self.spec.k + 1
        auto_block = decode_block == "auto"
        self.decode_block = 8 if auto_block else int(decode_block)
        self._decode_block_mode = "auto" if auto_block else "fixed"
        if self.spec is not None:
            # the spec loop owns step granularity: one draft+verify wave
            # per engine step commits up to k+1 tokens per slot, so the
            # decode-chunk latency probe is meaningless (and never run)
            self.decode_block = self.spec.k + 1
            self._decode_block_mode = "spec"
        self.reset()
        if auto_block and self.spec is None:
            # spec config is part of the key: toggling spec on/off across
            # engines in one process must not replay a stale probe
            # weights_layout is part of the key: a bf16-probed block must
            # not be replayed for the packed-weight step function (different
            # per-step cost) or vice versa
            # mesh shape is part of the key: a tp=2 probe's per-step cost
            # (collectives, per-device gemm sizes) must not be replayed
            # for tp=1 or a different mesh, and vice versa
            probe_key = (cfg.name, policy, slots, kv_layout, cache_len,
                         max_new_cap, block_size if self._paged else 0,
                         self.num_blocks if self._paged else 0,
                         self.table_len if self._paged else 0,
                         weights_layout,
                         tuple(sorted(self.mesh.shape.items()))
                         if self.mesh is not None else None)
            if probe_key not in _PROBE_CACHE:
                _PROBE_CACHE[probe_key] = self._probe_decode_block()
            self.decode_block = _PROBE_CACHE[probe_key]
        # greedy_only is a trace-time constant: two compiled variants at
        # most. The state pytree is donated so the slot caches are updated
        # in place (no 2x cache copy per chunk; a no-op on backends
        # without donation support, e.g. CPU).
        #
        # Every wave goes through _wave(family, ...): the registry runs it
        # under the mesh and records a shape signature whenever the call
        # triggered a fresh compile, so stats()["compile_variants"] and
        # the retrace-budget audit read live per-family variant counts.
        self._wave_jits: Dict[str, object] = {}
        self._wave_variants: Dict[str, List[str]] = {}
        self._decode_jit = self._wave("decode", jax.jit(
            self._decode_chunk, static_argnums=(2,), donate_argnums=(1,)))
        self._admit_jit = self._wave("admit_dense", jax.jit(
            self._admit_batch, static_argnums=(10,), donate_argnums=(1,)))
        if self._paged:
            self._admit_paged_jit = self._wave("admit_paged", jax.jit(
                self._admit_batch_paged, static_argnums=(11,),
                donate_argnums=(1,)))
            # one compiled program advances a whole wave of tail/chunked
            # prefills: per-row (slot, c0, tail_len), pad rows dropped
            self._tail_jit = self._wave("tail", jax.jit(
                self._tail_wave, static_argnums=(6,), donate_argnums=(1,)))
            # swap-in restore: one donated scatter for the whole payload
            # (per-leaf .at[].set calls would each materialize a second
            # pool — transient 2x cache HBM on every restore)
            self._swap_in_jit = self._wave("swap_in", jax.jit(
                self._swap_in_scatter, donate_argnums=(0,)))
            # donated so the COW clone rewrites pool blocks in place
            # instead of materializing a second pool
            self._cow_jit = self._wave("cow", jax.jit(
                self._cow_copy, donate_argnums=(0,)))
        if self.spec is not None:
            # draft loop: k+1 draft decode steps in one compiled scan
            # (the last step only commits the final proposal's KV)
            self._draft_jit = self._wave("spec_draft", jax.jit(
                self._spec_draft, static_argnums=(8,), donate_argnums=(1,)))
            # verify-wave: commit + all-position logits + acceptance +
            # rollback of the device counters, one compiled program
            self._spec_jit = self._wave("spec_verify", jax.jit(
                self._spec_wave, static_argnums=(5, 6), donate_argnums=(1,)))
            # draft-side admission: prefill the draft cache for freshly
            # armed decode residents
            self._draft_admit_jit = self._wave("admit_draft", jax.jit(
                self._draft_admit, donate_argnums=(1,)))

    def _wave(self, family: str, jitted):
        """Register a compiled wave family and wrap its jit for serving.

        The wrapper runs the call inside the mesh context (like
        ``_under_mesh``) and compares the jit's compile-cache size across
        the call: when it grew, this call traced a fresh variant, and its
        argument shape signature is recorded. Steady-state overhead is two
        integer reads per wave — the signature is only built on compiles.
        """
        self._wave_jits[family] = jitted
        variants = self._wave_variants.setdefault(family, [])
        mesh = self.mesh

        def run(*args):
            try:
                before = jitted._cache_size()
            except Exception:
                before = None
            if mesh is not None:
                with mesh:
                    out = jitted(*args)
            else:
                out = jitted(*args)
            if before is not None:
                try:
                    grew = jitted._cache_size() > before
                except Exception:
                    grew = False
                if grew:
                    variants.append(_arg_signature(args))
                    # taint the enclosing open span so the trace-side
                    # compile-vs-execute split matches this registry
                    self.trace.annotate(compiled=family)
            return out
        return run

    def _tail_wave(self, params, cache, toks, slots_, c0s, clens, hb):
        """Tail-wave forward: one batched ``prefill_tail`` window over
        every in-progress tail/chunked prefill (per-row slot/c0/len)."""
        return prefill_tail(self.cfg, params, self.ctx, toks, cache,
                            slots_, c0s, clens, hist_blocks=hb)

    def _cow_copy(self, cache, src, dst):
        """Copy-on-write block clone: pool leaves copy ``src`` block rows
        onto ``dst`` (sentinel dsts drop), everything else passes through."""
        def cp(path, leaf):
            if getattr(path[-1], "key", None) in _POOL_KEYS:
                return copy_pool_blocks(leaf, src, dst, mesh=self.mesh)
            return leaf
        return jax.tree_util.tree_map_with_path(cp, cache)

    def _under_mesh(self, fn):
        """Wrap a compiled program so it traces and runs inside the mesh
        context — the bare-axis ``shard_hint`` constraints in the model
        code resolve against it, and GSPMD partitions the wave across the
        mesh instead of batching per-device copies. Identity without a
        mesh."""
        if self.mesh is None:
            return fn
        mesh = self.mesh

        def run(*args, **kwargs):
            with mesh:
                return fn(*args, **kwargs)
        return run

    def _served_weight_leaves(self) -> List:
        """The weight leaves the serve forward actually streams: under
        w4a8 the packed export planes, under bf16 the whole tree."""
        if self.weights_layout != "w4a8":
            return jax.tree.leaves(self.params)
        flat, _ = jax.tree_util.tree_flatten_with_path(self.params)
        return [leaf for path, leaf in flat
                if any(getattr(p, "key", None) == "w4a8" for p in path)]

    def _shard_state(self, state: Dict) -> Dict:
        """Commit the device state pytree to the mesh: KV pool sharded
        over "model" on the KV-head dim, everything else replicated."""
        if self.mesh is None:
            return state
        return jax.device_put(
            state, serve_state_shardings(self.cfg, self.mesh, state))

    # ------------------------------------------------------------------
    # Compiled programs
    # ------------------------------------------------------------------

    def _decode_chunk(self, params, state, greedy_only):
        """Up to ``decode_block`` decode steps, entirely on device."""
        slots, cap = self.slots, self.max_new_cap

        def cond(st):
            return (st["i"] < self.decode_block) & jnp.any(st["active"])

        def body(st):
            logits, cache = decode_step(self.cfg, params, self.ctx,
                                        st["tokens"], st["cache"])
            keys_t = fold_step(st["keys"], st["n_gen"])
            toks = sample_tokens(logits[:, -1], keys_t, st["temp"],
                                 st["top_k"], greedy_only=greedy_only)
            act = st["active"]
            # commit only active slots; inactive rows scatter out of range
            row = jnp.where(act, st["n_gen"], cap)
            out = st["out"].at[jnp.arange(slots), row].set(toks, mode="drop")
            n_gen = st["n_gen"] + act.astype(jnp.int32)
            still = act & (toks != st["eos"]) & (n_gen < st["max_new"])
            return {**st, "cache": cache,
                    "tokens": jnp.where(act[:, None], toks[:, None],
                                        st["tokens"]),
                    "out": out, "n_gen": n_gen, "active": still,
                    "steps": st["steps"] + 1,
                    "committed": st["committed"] + jnp.sum(
                        act.astype(jnp.int32)),
                    "i": st["i"] + 1}

        st = {**state, "i": jnp.int32(0)}
        st = jax.lax.while_loop(cond, body, st)
        st.pop("i")
        return st

    def _post_prefill_state(self, state, new_cache, first, slot_idx, eos,
                            max_new, temp, top_k, keys):
        """Scatter n freshly-prefilled rows' sampling/output state into
        their slots (shared by the dense and paged admission programs)."""
        out = state["out"].at[slot_idx].set(0, mode="drop")
        return {**state, "cache": new_cache,
                "tokens": state["tokens"].at[slot_idx, 0].set(first,
                                                              mode="drop"),
                "out": out.at[slot_idx, 0].set(first, mode="drop"),
                "n_gen": state["n_gen"].at[slot_idx].set(1, mode="drop"),
                "active": state["active"].at[slot_idx].set(
                    (first != eos) & (max_new > 1), mode="drop"),
                "eos": state["eos"].at[slot_idx].set(eos, mode="drop"),
                "max_new": state["max_new"].at[slot_idx].set(max_new,
                                                             mode="drop"),
                "temp": state["temp"].at[slot_idx].set(temp, mode="drop"),
                "top_k": state["top_k"].at[slot_idx].set(top_k, mode="drop"),
                "keys": state["keys"].at[slot_idx].set(keys, mode="drop")}

    def _admit_batch(self, params, state, tokens, lengths, slot_idx, eos,
                     max_new, temp, top_k, keys, greedy_only):
        """One batched prefill + scatter of n fresh rows into their slots.

        Rows may be padding (the host pads the admission batch up to a
        power of two to bound compile variants); their ``slot_idx`` is
        out of range and every scatter drops them.
        """
        batch = {"tokens": tokens}
        if self._pad_ok:
            batch["lengths"] = lengths
        logits, cache_n = prefill(self.cfg, params, self.ctx, batch,
                                  cache_budget=self.cache_len)
        n = tokens.shape[0]
        first = sample_tokens(logits[:, 0],
                              fold_step(keys, jnp.zeros((n,), jnp.int32)),
                              temp, top_k, greedy_only=greedy_only)
        cache = state["cache"]
        # cache leaves are scan-stacked (repeat, slots, ...); position (slots,)
        segments = [jax.tree.map(
            lambda d, s: d.at[:, slot_idx].set(s, mode="drop"), ds, ss)
            for ds, ss in zip(cache["segments"], cache_n["segments"])]
        new_cache = {"segments": segments,
                     "position": cache["position"].at[slot_idx].set(
                         cache_n["position"], mode="drop")}
        return self._post_prefill_state(state, new_cache, first, slot_idx,
                                        eos, max_new, temp, top_k, keys)

    def _admit_batch_paged(self, params, state, tokens, lengths, slot_idx,
                           blk_ids, eos, max_new, temp, top_k, keys,
                           greedy_only):
        """Paged admission: prefill emits block-shaped caches, scattered
        into the global pool through the rows' allocated block ids.

        ``blk_ids`` (n, nb) int32: pool destinations for each row's prompt
        blocks; entries past a row's ``ceil(len/bs)`` blocks (and whole
        padding rows) hold the out-of-range sentinel and drop.
        """
        batch = {"tokens": tokens, "lengths": lengths}
        logits, cache_n = prefill(self.cfg, params, self.ctx, batch,
                                  page_size=self.block_size)
        n = tokens.shape[0]
        first = sample_tokens(logits[:, 0],
                              fold_step(keys, jnp.zeros((n,), jnp.int32)),
                              temp, top_k, greedy_only=greedy_only)
        cache = state["cache"]

        def scatter(path, d, s):
            if getattr(path[-1], "key", None) in _POOL_KEYS:
                # d (rep, NB, ...), s (rep, n, nb, ...): block scatter
                return d.at[:, blk_ids].set(s, mode="drop")
            return d.at[:, slot_idx].set(s, mode="drop")   # per-slot leaves

        segments = [jax.tree_util.tree_map_with_path(scatter, ds, ss)
                    for ds, ss in zip(cache["segments"],
                                      cache_n["segments"])]
        new_cache = {"segments": segments,
                     "position": cache["position"].at[slot_idx].set(
                         cache_n["position"], mode="drop"),
                     "block_tbl": cache["block_tbl"]}
        return self._post_prefill_state(state, new_cache, first, slot_idx,
                                        eos, max_new, temp, top_k, keys)

    # ------------------------------------------------------------------
    # Speculative decoding: draft scan + verify-wave (compiled)
    # ------------------------------------------------------------------

    def _spec_draft(self, dparams, dcache, tokens, temp, top_k, keys,
                    n_gen, lens, greedy_only):
        """Draft ``k`` proposals per slot, entirely on device.

        The draft cache's counters are first re-clamped to ``lens`` (the
        target's committed extent) — that is the draft-side rollback of
        positions over-drafted before the previous wave's rejections.
        The scan runs ``k + 1`` draft decode steps: step j consumes the
        previous proposal (step 0 the slot's last committed token) and
        samples proposal j+1 with the plain-decode key stream
        ``fold_in(key, n_gen + j)`` — so a self-draft proposes exactly
        the tokens plain decode would emit and everything is accepted.
        The final step only commits its input's KV (its proposal is
        discarded): the draft cache ends the wave covering every token
        the target might accept. In ``rejection`` mode the per-proposal
        draft distribution rides along for the acceptance test.
        """
        k = self.spec.k
        dcache = {"segments": _clamp_lengths(dcache["segments"], lens),
                  "position": lens}
        want_q = self.spec.accept_mode == "rejection" and not greedy_only

        def step(carry, j):
            tok, cache = carry
            logits, cache = decode_step(self.draft_cfg, dparams,
                                        self.draft_ctx, tok, cache)
            nxt = sample_tokens(logits[:, -1], fold_step(keys, n_gen + j),
                                temp, top_k, greedy_only=greedy_only)
            q = (token_probs(logits[:, -1], temp, top_k) if want_q
                 else jnp.zeros((tok.shape[0], 0), jnp.float32))
            return (nxt[:, None], cache), (nxt, q)

        (_, dcache), (dt, dq) = jax.lax.scan(
            step, (tokens, dcache), jnp.arange(k + 1, dtype=jnp.int32))
        dtoks = jnp.moveaxis(dt[:k], 0, 1)                     # (S, k)
        dqs = jnp.moveaxis(dq[:k], 0, 1) if want_q else None   # (S, k, V)
        return dtoks, dqs, dcache

    def _spec_wave(self, params, state, dtoks, dq, tail_len, hist_blocks,
                   greedy_only):
        """Verify every resident's drafted window in ONE compiled call
        and commit the accepted prefix.

        The window ``[last_token, draft_1..draft_k]`` is verified by
        ``models.spec_verify`` (per-row ``(c0, tail_len)`` batched-chunk
        contract, decode-exact numerics), the target's own samples are
        drawn with the plain-decode key stream, and acceptance picks how
        many tokens commit: the leading draft matches plus one target
        token (the correction at the first mismatch, or the bonus when
        everything survives), truncated at the first committed EOS and
        the row's remaining ``max_new`` budget. Rejected positions roll
        back on device here — per-layer ``length`` and ``position``
        re-clamp to the accepted extent, so the stale KV past it is
        unreadable — and the host releases their whole blocks via
        ``BlockAllocator.trim`` right after (the per-slot committed
        count is recovered host-side from the harvest's ``n_gen`` fetch,
        keeping the wave at one sync like a decode chunk).
        """
        S, C = self.slots, self.spec.k + 1
        cap = self.max_new_cap
        cache = state["cache"]
        c0 = cache["position"]
        slot_idx = jnp.arange(S, dtype=jnp.int32)
        window = jnp.concatenate([state["tokens"], dtoks], axis=1)
        logits, cache = spec_verify(self.cfg, params, self.ctx, window,
                                    cache, slot_idx, c0, tail_len,
                                    hist_blocks=hist_blocks)
        n_gen, act = state["n_gen"], state["active"]
        # one flattened (S*C)-row sampling call: per-row ops (argmax /
        # top-k mask / per-key categorical) are exactly what C sequential
        # decode steps would run, at a C-independent op count
        V = logits.shape[-1]
        flat = logits.reshape(S * C, V)
        keys_rep = jnp.repeat(state["keys"], C, axis=0)
        ctr = (n_gen[:, None] + jnp.arange(C)[None]).reshape(S * C)
        temp_rep = jnp.repeat(state["temp"], C)
        topk_rep = jnp.repeat(state["top_k"], C)
        tt = sample_tokens(flat, fold_step(keys_rep, ctr), temp_rep,
                           topk_rep,
                           greedy_only=greedy_only).reshape(S, C)
        n_draft = jnp.maximum(tail_len - 1, 0)
        if self.spec.accept_mode == "rejection" and not greedy_only:
            p = token_probs(flat, temp_rep, topk_rep).reshape(S, C, V)
            n_acc, committed = accept_rejection(dtoks, dq, p, tt,
                                                state["keys"], n_gen,
                                                n_draft)
        else:
            n_acc, committed = accept_exact(dtoks, tt, n_draft), tt
        m = n_acc + 1
        is_eos = committed == state["eos"][:, None]
        m = jnp.where(jnp.any(is_eos, axis=1),
                      jnp.minimum(m, jnp.argmax(is_eos, axis=1) + 1), m)
        m = jnp.where(act, jnp.minimum(m, jnp.maximum(tail_len, 1)), 0)
        jj = jnp.arange(C)[None]
        row = jnp.where(jj < m[:, None], n_gen[:, None] + jj, cap)
        out = state["out"].at[slot_idx[:, None], row].set(committed,
                                                          mode="drop")
        n_gen2 = n_gen + m
        lastj = jnp.maximum(m - 1, 0)[:, None]
        last = jnp.take_along_axis(committed, lastj, axis=1)[:, 0]
        hit_eos = jnp.take_along_axis(is_eos, lastj, axis=1)[:, 0]
        still = act & ~hit_eos & (n_gen2 < state["max_new"])
        new_len = c0 + m
        cache = {"segments": _clamp_lengths(cache["segments"], new_len),
                 "position": new_len, "block_tbl": cache["block_tbl"]}
        return {**state, "cache": cache,
                "tokens": jnp.where(act[:, None], last[:, None],
                                    state["tokens"]),
                "out": out, "n_gen": n_gen2, "active": still,
                "steps": state["steps"] + 1,
                "committed": state["committed"] + jnp.sum(m)}

    def _draft_admit(self, dparams, dcache, tokens, lengths, slot_idx):
        """Prefill the draft model's dense cache rows for freshly armed
        decode residents (padding rows' ``slot_idx`` sentinel drops),
        mirroring the dense half of ``_admit_batch``."""
        batch = {"tokens": tokens, "lengths": lengths}
        _, cache_n = prefill(self.draft_cfg, dparams, self.draft_ctx, batch,
                             cache_budget=self._draft_cache_len)
        segments = [jax.tree.map(
            lambda d, s: d.at[:, slot_idx].set(s, mode="drop"), ds, ss)
            for ds, ss in zip(dcache["segments"], cache_n["segments"])]
        return {"segments": segments,
                "position": dcache["position"].at[slot_idx].set(
                    cache_n["position"], mode="drop")}

    # ------------------------------------------------------------------
    # Request lifecycle (host side)
    # ------------------------------------------------------------------

    def _blank_state(self) -> Dict:
        slots = self.slots
        if self._paged:
            cache = init_cache(self.cfg, self.ctx, slots, self.cache_len,
                               num_blocks=self.num_blocks,
                               page_size=self.block_size,
                               table_len=self.table_len)
        else:
            cache = init_cache(self.cfg, self.ctx, slots, self.cache_len)
        return {
            "cache": cache,
            "tokens": jnp.zeros((slots, 1), jnp.int32),
            "out": jnp.zeros((slots, self.max_new_cap), jnp.int32),
            "n_gen": jnp.zeros((slots,), jnp.int32),
            "active": jnp.zeros((slots,), bool),
            "eos": jnp.full((slots,), -1, jnp.int32),
            "max_new": jnp.ones((slots,), jnp.int32),
            "temp": jnp.zeros((slots,), jnp.float32),
            "top_k": jnp.zeros((slots,), jnp.int32),
            "keys": jnp.zeros((slots, 2), jnp.uint32),
            "steps": jnp.int32(0),
            "committed": jnp.int32(0),
        }

    def reset(self) -> None:
        """Clear all serving state but keep compiled programs warm.

        Drops every queued / resident / swapped request, reinitializes
        the cache pytree and the block allocator (paged), zeroes all
        stats, and replaces the scheduler with a fresh one of the same
        policy. Compiled programs and the ``decode_block="auto"`` probe
        result survive, so a reset-and-rerun (the benchmark pattern)
        pays no recompile. Requests submitted before the reset must not
        be resubmitted to the old engine's allocator state — their
        prefix-lookup memos are invalidated by an epoch bump.
        """
        self.state = self._shard_state(self._blank_state())
        # monotone epoch invalidates per-request lookup memos across
        # resets (an id()-based token could collide on address reuse)
        self._alloc_epoch = getattr(self, "_alloc_epoch", -1) + 1
        self.alloc = (BlockAllocator(self.num_blocks, self.block_size,
                                     self.slots, self.table_len,
                                     prefix_cache=self.prefix_cache)
                      if self._paged else None)
        self._slot_req = {}
        self._written: Dict[int, int] = {}   # paged: tokens committed/slot
        self._tbl_dirty = False              # host table mirror vs device
        self._tail_jobs: List[Dict] = []     # in-progress tail prefills
        self._swapped: List[Dict] = []       # preempted, awaiting restore
        self._admit_seq: Dict[int, int] = {}     # slot -> admission order
        self._seq = 0
        self._max_residents = 0
        self.scheduler = Scheduler(self.scheduler.policy, trace=self.trace)
        # a fresh run gets a fresh observability window: reruns (the
        # benchmark warmup→reset→timed pattern) must not inherit the
        # previous pass's spans or histogram mass
        self.trace.clear()
        self.metrics.reset()
        self._step_idx = 0
        self._pred_per_tok: Optional[float] = None   # fastest s/prompt-tok
        self._pred_round_s: Optional[float] = None   # fastest decode round
        self._host = {"decode_s": 0.0, "decode_rounds": 0,
                      "prefill_s": 0.0, "prefill_calls": 0,
                      "prefill_tokens": 0, "prefill_chunks": 0,
                      "prompt_tokens": 0, "prefix_hit_tokens": 0,
                      "cow_copies": 0, "preemptions": 0,
                      "swap_out_bytes": 0, "swap_in_bytes": 0,
                      "swap_s": 0.0}
        if self.spec is not None:
            self._draft_cache = init_cache(self.draft_cfg, self.draft_ctx,
                                           self.slots,
                                           self._draft_cache_len)
            if self.mesh is not None:
                self._draft_cache = jax.device_put(
                    self._draft_cache,
                    serve_cache_shardings(self.draft_cfg, self.mesh,
                                          self._draft_cache))
            self._host.update({"spec_waves": 0, "spec_drafted": 0,
                               "spec_accepted": 0, "spec_rolled_back": 0,
                               "spec_draft_prefill_tokens": 0})
        self._cache_bytes = sum(
            leaf.nbytes for seg in self.state["cache"]["segments"]
            for leaf in jax.tree.leaves(seg))

    def submit(self, req: Request) -> None:
        """Enqueue one request for serving.

        Args:
            req: a :class:`Request`. ``prompt`` is a 1-D int32 token-id
                array; ``max_new_tokens`` bounds generation (the first
                token comes from prefill); ``temperature <= 0`` means
                greedy and ``top_k == 0`` disables filtering;
                ``deadline_ms`` / ``priority`` feed the ``edf``
                scheduler policy and ``slo_shed`` admission control;
                ``on_tokens`` (if set) receives every freshly decoded
                span as ``on_tokens(req, tokens, done)``.

        Returns:
            None. The request is queued; the engine admits it on a later
            :meth:`step`. Completion is signalled by ``req.done`` (tokens
            in ``req.generated``), by the ``on_tokens`` callback, or by
            ``req.shed`` if SLO admission control rejected it.

        Raises:
            ValueError: if the request can *never* be admitted on this
                engine — ``max_new_tokens`` above ``max_new_cap``,
                ``top_k`` above ``TOP_K_CAP``, or a token footprint
                (``prompt + max_new_tokens - 1``) exceeding
                ``max_seq_len`` / the block table / the pool (paged) or
                ``cache_len`` (dense full-attention). The message names
                the computed need and the knob to raise.
        """
        if req.max_new_tokens > self.max_new_cap:
            raise ValueError(
                f"max_new_tokens={req.max_new_tokens} exceeds this engine's "
                f"max_new_cap={self.max_new_cap} (the on-device token "
                f"buffer); construct ServeEngine with a larger max_new_cap")
        if req.top_k > TOP_K_CAP:
            raise ValueError(f"top_k={req.top_k} exceeds TOP_K_CAP="
                             f"{TOP_K_CAP} (static sampling bound)")
        # peak cache occupancy is prompt + max_new - 1: the last sampled
        # token is returned but its KV is never written while resident
        need = len(req.prompt) + req.max_new_tokens - 1
        if self._paged:
            if need > self.max_seq_len:
                raise ValueError(
                    f"request needs {need} cache tokens (prompt "
                    f"{len(req.prompt)} + max_new_tokens "
                    f"{req.max_new_tokens} - 1) but max_seq_len="
                    f"{self.max_seq_len}; raise max_seq_len or shorten "
                    f"the request")
            nb = self.alloc.blocks_for_tokens(need)
            if nb > self.table_len:
                raise ValueError(
                    f"request needs {nb} block-table entries ({need} tokens "
                    f"at block_size={self.block_size}) but the block table "
                    f"is only table_len={self.table_len} entries wide, so "
                    f"it can never be admitted; raise table_len or "
                    f"max_seq_len")
            if nb > self.num_blocks:
                raise ValueError(
                    f"request needs {nb} cache blocks ({need} tokens at "
                    f"block_size={self.block_size}) but the pool only has "
                    f"num_blocks={self.num_blocks}, so it can never be "
                    f"admitted; raise num_blocks")
        elif self._cache_bound and need > self.cache_len:
            raise ValueError(
                f"request needs {need} cache tokens (prompt "
                f"{len(req.prompt)} + max_new_tokens {req.max_new_tokens} "
                f"- 1) but cache_len={self.cache_len} on a full-attention "
                f"model; raise cache_len or shorten the request")
        self.scheduler.submit(req)

    def _note_residency(self) -> None:
        n = len(self._slot_req) + len(self._tail_jobs)
        self._max_residents = max(self._max_residents, n)

    # ------------------------------------------------------------------
    # SLO-aware admission + streaming drain
    # ------------------------------------------------------------------

    def _predict_ttft_s(self, backlog_tokens: int) -> float:
        """Estimate seconds until a queued request's first token given
        ``backlog_tokens`` prompt tokens must prefill before it (requests
        ahead in policy order plus its own prompt). Fitted from this
        engine's own measured rates — prefill seconds per prompt token
        plus one decode round (the wave in flight when it reaches the
        head) — so the estimate tracks the deployment, not a constant.
        Returns 0.0 until the engine has measured anything (a cold engine
        never sheds blind). Rates are the *fastest* observed per call —
        a min, not a mean — so the one-time XLA compile cost of each
        program variant (seconds, folded into the first call's wall
        time) can't masquerade as steady-state service time and shed the
        whole queue on a freshly constructed engine."""
        if self._pred_per_tok is None:
            return 0.0
        return (self._pred_per_tok * backlog_tokens
                + (self._pred_round_s or 0.0))

    def _note_rate(self, attr: str, value: float) -> None:
        """Min-track a measured rate for the TTFT predictor."""
        cur = getattr(self, attr)
        setattr(self, attr, value if cur is None else min(cur, value))

    def _shed_overdue(self) -> None:
        """Shed-load pass before admission (``slo_shed != "none"``):
        requests whose predicted TTFT already exceeds their deadline are
        rejected (``req.shed = True``, stream closed with no tokens) or
        downgraded to best-effort, per the engine's ``slo_shed`` mode."""
        if self.slo_shed == "none" or not self.scheduler.pending:
            return
        for r in self.scheduler.shed_overdue(self._predict_ttft_s,
                                             self.slo_shed):
            r.shed = True
            r.done = True
            self.trace.event("shed", uid=r.uid)
            self._emit_stream(r, (), done=True)

    @staticmethod
    def _emit_stream(req, toks, done: bool) -> None:
        """Deliver freshly decoded tokens to a streaming request's
        ``on_tokens`` callback (no-op for non-streaming requests)."""
        if req.on_tokens is not None:
            req.on_tokens(req, list(toks), done)
            req._streamed += len(toks)
        elif done:
            req._streamed = len(req.generated)

    def _admit(self) -> None:
        self._shed_overdue()
        if self._paged:
            self._admit_paged()
            return
        free = self._free_slots()
        if not free or not self.scheduler.pending:
            return
        reqs = self.scheduler.select(len(free),
                                     equal_length_only=not self._pad_ok)
        if not reqs:
            return
        self._admit_wave(reqs, free[:len(reqs)])
        self._note_residency()

    def _free_slots(self) -> List[int]:
        busy = set(self._slot_req)
        busy.update(j["slot"] for j in self._tail_jobs)
        return [s for s in range(self.slots) if s not in busy]

    def _affinity_key(self, req):
        """Grouping key for prefix-aware scheduling: requests whose
        prompts extend the same cached chain share its block-id tuple, so
        the scheduler pulls them back-to-back and the chain is admitted
        while still hot in the allocator's LRU (a miss returns None — no
        grouping). Ordering is a *hint*, so unlike admission (which needs
        version-exact block ids) a stale key is acceptable: each request
        pays one real lookup on first sight and then reuses its last
        known key until some other path (head check, wave predicate)
        re-looks it up for real — the index version bumps on every wave
        window, and re-hashing the whole queue per engine step would put
        O(queue x prompt) sha256 digests on the admission hot path."""
        ver2 = (id(self), self._alloc_epoch)
        memo = getattr(req, "_prefix_hit", None)
        if memo is not None and memo[0] == ver2 + (
                self.alloc.index_version,):
            ids = memo[1][0]
            return tuple(ids) if ids else None
        hint = getattr(req, "_affinity_memo", None)
        if hint is not None and hint[0] == ver2:
            return hint[1]
        ids = self._lookup(req)[0]
        return tuple(ids) if ids else None

    def _admit_paged(self) -> None:
        """Paged admission loop. Swapped-out (preempted) requests restore
        ahead of new work (head-of-line, so preemption can't starve).
        Each new request is first looked up in the prefix cache: a request
        with a cached prefix maps the hit blocks (refcount++) and admits
        through the tail-prefill path, computing only the uncached tail;
        prompts longer than ``prefill_chunk`` take the same path window by
        window. Up to ``tail_batch`` tail admissions ride concurrently —
        each engine step advances all of them in ONE compiled wave
        (``_advance_tail_jobs``), so simultaneous prefix-hit arrivals no
        longer serialize. With ``prefix_affinity`` the queue is grouped so
        requests sharing a cached chain admit back-to-back. Everything
        else admits as a batched cold wave under the free-block criterion
        with head-of-line blocking."""
        if self._swapped:
            self._try_swap_in()
            if self._swapped:
                return              # restore before admitting new work
        gk = self._affinity_key if self.prefix_affinity else None
        held: set = set()
        while self.scheduler.pending > len(held):
            free = self._free_slots()
            if not free:
                return
            # chains with a tail admission in flight stay "hot": their
            # queued sharers rank ahead so the chain's LRU blocks are
            # mapped again before anything can evict them
            hot = ({j["akey"] for j in self._tail_jobs
                    if j.get("akey") is not None} if gk else ())
            head = self.scheduler.first(group_key=gk, hot=hot, skip=held)
            if head is None:
                return
            plen = len(head.prompt)
            hit_ids, cached, partial = self._lookup(head)
            if self._dedup_hold(head, cached):
                # cross-wave dedup: this head waits a wave for the
                # in-flight sharer to register — but only IT is held;
                # unrelated work behind it still admits this step
                held.add(head)
                continue
            if cached or plen > self.prefill_chunk:
                if len(self._tail_jobs) >= self.tail_batch:
                    return          # wave is full: head waits its turn
                slot = free[0]
                eff = self._paged_admit_slot(slot, head, hit_ids, partial,
                                             cached)
                if eff is None:
                    return              # pool exhausted: head waits
                self.scheduler.take(head)
                self._host["prefix_hit_tokens"] += eff
                self._tail_jobs.append({"req": head, "slot": slot,
                                        "c0": eff,
                                        "akey": tuple(hit_ids) or None})
                self._note_residency()
                continue
            taken: List[int] = []
            batch_reqs: List = []

            def ok(r):
                if len(r.prompt) > self.prefill_chunk:
                    return False        # long prompt: chunked next round
                if r is not head and self._lookup(r)[1]:
                    return False        # cached prefix: tail path next round
                bs = self.block_size
                if self.prefix_cache and len(r.prompt) - 1 >= bs and any(
                        len(q.prompt) >= bs
                        and np.array_equal(np.asarray(r.prompt[:bs]),
                                           q.prompt[:bs])
                        for q in batch_reqs):
                    # cross-wave dedup: r shares >= one full block with a
                    # request already in THIS forming wave; co-admitting
                    # would compute the shared content twice. Held one
                    # wave, it prefix-hits the blocks the wave registers
                    # (only the first block is compared: that is the
                    # whole trigger condition, so cost stays O(bs))
                    return False
                if self._paged_admit_slot(free[len(taken)], r, (),
                                          False, 0) is None:
                    return False
                taken.append(free[len(taken)])
                batch_reqs.append(r)
                return True

            reqs = self.scheduler.select(len(free), admit_ok=ok,
                                         group_key=gk, hot=hot, skip=held)
            if not reqs:
                return
            # lazy prefill allocation: just the prompt's blocks for now
            for s, r in zip(taken, reqs):
                self._ensure(s, len(r.prompt))
            self._admit_wave(reqs, taken, paged=True)
            self._note_residency()

    def _lookup(self, req):
        """Prefix-cache lookup memoized per request against the allocator
        identity + index version, so re-walking the queue every engine
        step doesn't re-hash prompts (or inflate the lookup stats) while
        nothing changed — and a request resubmitted after ``reset()`` (or
        to another engine) can't replay block ids from a dead pool."""
        if not self.prefix_cache:
            return (), 0, False
        ver = (id(self), self._alloc_epoch, self.alloc.index_version)
        memo = getattr(req, "_prefix_hit", None)
        if memo is not None and memo[0] == ver:
            return memo[1]
        hit = self.alloc.lookup(req.prompt)
        req._prefix_hit = (ver, hit)
        # refresh the affinity hint whenever a real lookup runs (see
        # _affinity_key: grouping tolerates staleness, admission doesn't)
        req._affinity_memo = (ver[:2], tuple(hit[0]) or None)
        return hit

    def _dedup_hold(self, req, cached: int) -> bool:
        """Cross-wave dedup (tail path): when ``req`` extends the same
        chain an in-flight tail admission is still prefilling, admitting
        it now would recompute the shared content. Hold it while any
        in-flight job has at least one block of overlap ``req`` hasn't
        prefix-hit yet — a wave later the job's freshly registered
        blocks turn the overlap into a hit. Bounded: jobs leave
        ``_tail_jobs`` in finitely many waves (completion or
        preemption), registration is monotone, and the gap closes once
        the registered extent covers the overlap."""
        if not self.prefix_cache or not self._tail_jobs:
            return False
        # the hold triggers iff >= one whole block of overlap remains
        # unhit, i.e. the first cached + block_size tokens agree — so
        # only that slice is ever compared, keeping the per-step cost
        # O(block_size + cached) per in-flight job instead of O(prompt)
        need = cached + self.block_size
        if len(req.prompt) - 1 < need:
            return False
        head = np.asarray(req.prompt[:need])
        for job in self._tail_jobs:
            jp = job["req"].prompt
            if len(jp) >= need and np.array_equal(head, jp[:need]):
                return True
        return False

    def _paged_admit_slot(self, slot: int, req, hit_ids, partial: bool,
                          cached: int) -> Optional[int]:
        """Admit one request into ``slot``: map its shared prefix blocks
        and commit capacity under the engine's admission discipline.
        ``reserve`` debits the worst-case fresh-block count up front;
        ``optimistic`` physically allocates only the first tail window
        (the whole prompt for a wave row) and relies on preemption for
        later growth. Returns the effective cached-token count (0 when
        the prefix ended up unused), or None — leaving no state behind —
        when the pool can't take the request now."""
        plen = len(req.prompt)
        need = plen + req.max_new_tokens - 1
        if self.admission == "reserve":
            if not self.alloc.reserve(slot, need, shared=hit_ids,
                                      partial=partial):
                # a shared admission transiently needs more obtainable
                # blocks than an exclusive one (resurrecting LRU hits +
                # the split-block COW can exceed the pool on tiny pools);
                # when nothing is resident the pool will never get freer,
                # so fall back to an unshared reservation over deadlock
                idle = (not self._slot_req and not self._tail_jobs
                        and not self._swapped)
                if not (idle and hit_ids and self.alloc.reserve(slot, need)):
                    return None
                hit_ids, cached = (), 0
        else:
            self.alloc.register(slot, shared=hit_ids)
            try:
                self.alloc.ensure(slot, min(cached + self.prefill_chunk,
                                            plen))
            except PoolDry:
                self.alloc.release(slot)
                return None
        if hit_ids or self.admission == "optimistic":
            self._tbl_dirty = True
        self._admit_seq[slot] = self._seq
        self._seq += 1
        return cached

    def _admit_wave(self, reqs, taken, paged: bool = False) -> None:
        """One batched prefill admission (dense or paged)."""
        n = len(reqs)
        # pad the admission batch up to a power of two (dummy rows scatter
        # out of range and drop) so compile variants are O(log slots) per
        # length bucket instead of one per free-slot count
        n_pad = min(_pow2_ceil(n), self.slots)
        lens = np.ones((n_pad,), np.int32)
        lens[:n] = [len(r.prompt) for r in reqs]
        if self._pad_ok:
            L = -(-int(lens.max()) // self.prefill_bucket) \
                * self.prefill_bucket
        else:
            L = int(lens[0])
        toks = np.zeros((n_pad, L), np.int32)
        for i, r in enumerate(reqs):
            toks[i, :lens[i]] = r.prompt[:L]
        slot_idx = np.full((n_pad,), self.slots, np.int32)   # dummy: dropped
        slot_idx[:n] = taken[:n]
        keys = np.zeros((n_pad, 2), np.uint32)
        keys[:n] = np.stack([jax.random.fold_in(jax.random.PRNGKey(r.seed),
                                                r.uid) for r in reqs])

        def col(fn, fill, dtype):
            v = np.full((n_pad,), fill, dtype)
            v[:n] = [fn(r) for r in reqs]
            return jnp.asarray(v)

        greedy_only = all(r.temperature <= 0.0 for r in reqs)
        wave_tokens = int(sum(len(r.prompt) for r in reqs))
        with self.trace.span("prefill_wave", rows=n, tokens=wave_tokens,
                             paged=paged) as sp:
            common = (jnp.asarray(toks), jnp.asarray(lens),
                      jnp.asarray(slot_idx))
            tail = (col(lambda r: r.eos_id, -1, np.int32),
                    col(lambda r: r.max_new_tokens, 1, np.int32),
                    col(lambda r: r.temperature, 0.0, np.float32),
                    col(lambda r: r.top_k, 0, np.int32), jnp.asarray(keys),
                    greedy_only)
            if paged:
                # prefill emits ceil(L / block_size) blocks per row (bucket-
                # padded); rows point their own allocated blocks at the pool
                # and sentinel out both their tail blocks and the dummy rows
                nb = self.alloc.blocks_for_tokens(L)
                ids = np.full((n_pad, nb), self.num_blocks, np.int32)
                for i, (s, r) in enumerate(zip(taken, reqs)):
                    nb_i = self.alloc.blocks_for_tokens(len(r.prompt))
                    ids[i, :nb_i] = self.alloc.tables[s, :nb_i]
                self._push_tables()
                self.state = self._admit_paged_jit(
                    self.params, self.state, *common, jnp.asarray(ids),
                    *tail)
            else:
                self.state = self._admit_jit(self.params, self.state,
                                             *common, *tail)
            with self.trace.span("sync"):
                jax.block_until_ready(self.state["tokens"])
        self._host["prefill_s"] += sp.dt
        self._host["prefill_calls"] += 1
        self._host["prefill_tokens"] += n     # first token of each request
        self._host["prompt_tokens"] += wave_tokens
        self._note_rate("_pred_per_tok", sp.dt / max(wave_tokens, 1))
        self.scheduler.on_admitted(reqs)
        for r in reqs:
            # the admission wave sampled each row's first token, so TTFT
            # lands here (admission-wave granularity)
            tm = getattr(r, "_timing", None)
            if tm is not None:
                self.metrics.observe_ttft(tm.ttft)
            self.trace.event("first_token", uid=r.uid)
        for s, r in zip(taken, reqs):
            self._slot_req[s] = r
            if self._paged:
                self._written[s] = len(r.prompt)
                # content-address the freshly written prompt blocks so
                # later requests sharing the prefix skip their prefill
                self.alloc.register_prefix(s, r.prompt, len(r.prompt))
        if self.spec is not None:
            self._draft_prefill_rows([(s, r.prompt)
                                      for s, r in zip(taken, reqs)])

    def _advance_tail_jobs(self) -> None:
        """Advance EVERY in-progress tail/chunked prefill by one window —
        all jobs batched into a single compiled call (the tail-wave).
        ``c0`` starts at the cached-prefix length (0 for a plain long
        prompt), so a prefix-hit request computes only its uncached tail;
        per-row ``(c0, tail_len)`` offsets let rows at different depths of
        different prompts share the wave. One window per engine step:
        resident slots keep decoding between windows, so long prompts
        can't freeze everyone else's inter-token latency. Rows whose final
        window completes sample their first token and arm their slots
        together, exactly like a batched admission."""
        C = self.prefill_chunk
        with self.trace.span("schedule", kind="tail"):
            ready: List[Dict] = []
            lens: List[int] = []
            for job in list(self._tail_jobs):
                slot, c0 = job["slot"], job["c0"]
                cl = min(C, len(job["req"].prompt) - c0)
                # growth/COW may swap the job itself out on a dry pool
                # (_preempt_for never victimizes tail jobs, so jobs in this
                # loop can't evict each other)
                if not self._ensure(slot, c0 + cl):
                    continue
                if not self._cow_guard(slot, c0, c0 + cl):
                    continue
                ready.append(job)
                lens.append(cl)
        if not ready:
            return
        n = len(ready)
        done: List[Dict] = []
        with self.trace.span("tail_wave", rows=n,
                             tokens=int(sum(lens))) as sp:
            self._push_tables()
            n_pad = min(_pow2_ceil(n), self.slots)
            toks = np.zeros((n_pad, C), np.int32)
            slots_arr = np.full((n_pad,), self.slots, np.int32)  # pad: drop
            c0s = np.zeros((n_pad,), np.int32)
            clens = np.zeros((n_pad,), np.int32)
            hb_need = 1
            for i, (job, cl) in enumerate(zip(ready, lens)):
                c0 = job["c0"]
                toks[i, :cl] = job["req"].prompt[c0:c0 + cl]
                slots_arr[i] = job["slot"]
                c0s[i] = c0
                clens[i] = cl
                # table walk bounded by the tokens the deepest row can
                # touch, bucketed to a power of two to bound variants
                hb_need = max(hb_need, self.alloc.blocks_for_tokens(c0 + C))
            hb = min(_pow2_ceil(hb_need), self.table_len)
            logits, self.state["cache"] = self._tail_jit(
                self.params, self.state["cache"], jnp.asarray(toks),
                jnp.asarray(slots_arr), jnp.asarray(c0s),
                jnp.asarray(clens), hb)
            self._host["prefill_chunks"] += n
            self._host["prompt_tokens"] += int(sum(lens))
            rows: List[int] = []
            for i, (job, cl) in enumerate(zip(ready, lens)):
                job["c0"] += cl
                self.alloc.register_prefix(job["slot"], job["req"].prompt,
                                           job["c0"])
                if job["c0"] >= len(job["req"].prompt):
                    done.append(job)
                    rows.append(i)
            if done:
                reqs = [j["req"] for j in done]
                keys = jnp.asarray(np.stack(
                    [jax.random.fold_in(jax.random.PRNGKey(r.seed), r.uid)
                     for r in reqs]))
                temp = jnp.asarray([r.temperature for r in reqs],
                                   jnp.float32)
                top_k = jnp.asarray([r.top_k for r in reqs], jnp.int32)
                first = sample_tokens(
                    logits[np.asarray(rows)],
                    fold_step(keys, jnp.zeros((len(done),), jnp.int32)),
                    temp, top_k,
                    greedy_only=all(r.temperature <= 0.0 for r in reqs))
                self.state = self._post_prefill_state(
                    self.state, self.state["cache"], first,
                    jnp.asarray([j["slot"] for j in done], jnp.int32),
                    jnp.asarray([r.eos_id for r in reqs], jnp.int32),
                    jnp.asarray([r.max_new_tokens for r in reqs],
                                jnp.int32),
                    temp, top_k, keys)
                with self.trace.span("sync"):
                    jax.block_until_ready(self.state["tokens"])
            else:
                with self.trace.span("sync"):
                    jax.block_until_ready(self.state["cache"]["position"])
        self._host["prefill_s"] += sp.dt
        self._note_rate("_pred_per_tok", sp.dt / max(int(sum(lens)), 1))
        if not done:
            return
        self._host["prefill_calls"] += 1
        self._host["prefill_tokens"] += len(done)
        self.scheduler.on_admitted(reqs)
        for r in reqs:
            tm = getattr(r, "_timing", None)
            if tm is not None:
                self.metrics.observe_ttft(tm.ttft)
            self.trace.event("first_token", uid=r.uid)
        for j in done:
            self._tail_jobs.remove(j)
            self._slot_req[j["slot"]] = j["req"]
            self._written[j["slot"]] = len(j["req"].prompt)
        if self.spec is not None:
            # the tail computed only the uncached suffix, but the draft
            # has no prefix cache: its rows prefill the whole prompt
            self._draft_prefill_rows([(j["slot"], j["req"].prompt)
                                      for j in done])

    def _ensure(self, slot: int, n_tokens: int) -> bool:
        """Grow the slot's block table to cover ``n_tokens``. Under
        optimistic admission a dry pool preempts a victim — or, when no
        other resident can be evicted, swaps out ``slot`` itself. Returns
        False iff ``slot`` was swapped out (the caller must abandon its
        pending work for the slot)."""
        while True:
            try:
                if self.alloc.ensure(slot, n_tokens):
                    self._tbl_dirty = True
                return True
            except PoolDry:
                if not self._preempt_for(slot):
                    self._swap_out(slot)
                    return False

    def _cow_guard(self, slot: int, start_tok: int, end_tok: int) -> bool:
        """Resolve copy-on-write for a pending write of token positions
        ``[start_tok, end_tok)``: shared blocks in the range are replaced
        by fresh blocks and their int8 payload + scales cloned device-side
        *before* the write executes. A dry pool preempts like ``_ensure``
        (cow_range pre-checks its block need, so a raise applies nothing);
        returns False iff ``slot`` itself was swapped out."""
        while True:
            try:
                pairs = self.alloc.cow_range(slot, start_tok, end_tok)
                break
            except PoolDry:
                if not self._preempt_for(slot):
                    self._swap_out(slot)
                    return False
        if pairs:
            self._apply_cow(pairs)
        return True

    def _apply_cow(self, pairs) -> None:
        """Device-side block clones for resolved COW pairs, bucketed to a
        power of two (pad dsts sit on the sentinel and drop)."""
        n_pad = _pow2_ceil(len(pairs))
        src = np.zeros((n_pad,), np.int32)
        dst = np.full((n_pad,), self.num_blocks, np.int32)
        src[:len(pairs)] = [p[0] for p in pairs]
        dst[:len(pairs)] = [p[1] for p in pairs]
        with self.trace.span("cow", blocks=len(pairs)):
            self.state["cache"] = self._cow_jit(
                self.state["cache"], jnp.asarray(src), jnp.asarray(dst))
        self._host["cow_copies"] += len(pairs)
        self._tbl_dirty = True

    def _preempt_for(self, slot: int) -> bool:
        """Swap out one scheduler-chosen victim to free blocks. Candidates
        are the decode residents other than ``slot`` (in-progress tail
        jobs are never in ``_slot_req``, so they are implicitly protected
        — jobs in one wave can't evict each other). False when no other
        resident is preemptible."""
        cands = []
        for s, r in self._slot_req.items():
            if s == slot:
                continue
            remaining = (len(r.prompt) + r.max_new_tokens - 1
                         - self._written[s])
            cands.append((s, self._admit_seq.get(s, 0), remaining))
        victim = self.scheduler.pick_victim(cands, self.preempt)
        if victim is None:
            return False
        self._swap_out(victim)
        return True

    def _push_tables(self) -> None:
        """Push the host block-table mirror to the device iff it changed
        since the last push (block growth or a harvest-time release — the
        release is what retires freed slots' rows to the sentinel so their
        masked commits drop)."""
        if self._tbl_dirty:
            tbl = jnp.asarray(self.alloc.tables)
            if self.mesh is not None:
                # commit replicated: uncommitted single-device arrays
                # would make XLA pick a fresh sharding per program
                tbl = jax.device_put(tbl, NamedSharding(self.mesh, P()))
            self.state["cache"]["block_tbl"] = tbl
            self._tbl_dirty = False

    def _ensure_decode_blocks(self) -> None:
        """Grow resident slots' block tables to cover the upcoming decode
        chunk (lazy allocation at block-boundary crossings) and resolve
        copy-on-write for shared blocks in each slot's write range. Under
        optimistic admission either step may preempt a victim — possibly
        one of the slots this loop has yet to visit."""
        for s in list(self._slot_req):
            if s not in self._slot_req:
                continue            # preempted by an earlier iteration
            r = self._slot_req[s]
            cap = len(r.prompt) + r.max_new_tokens - 1
            w = self._written[s]
            target = min(w + self.decode_block, cap)
            if not self._ensure(s, target):
                continue            # s itself was swapped out
            if s in self._slot_req:
                self._cow_guard(s, w, target)
        self._push_tables()

    # ------------------------------------------------------------------
    # Preemption: swap-out / swap-in of quantized blocks
    # ------------------------------------------------------------------

    def _attn_layer_caches(self):
        """Every attention layer's cache dict, in a stable order (the
        swap payload lists follow this order)."""
        for seg in self.state["cache"]["segments"]:
            for li in sorted(seg, key=int):
                yield seg[li]

    def _gather_blocks(self, ids) -> List[Dict]:
        """Pull the listed pool blocks' int8 payload + scales to host
        buffers, one dict per attention layer — one batched device_get
        for the whole swap, not a sync per (layer, leaf)."""
        idx = jnp.asarray(np.asarray(ids, np.int32))
        gathered = [{k: layer["self"][k][:, idx] for k in _POOL_KEYS}
                    for layer in self._attn_layer_caches()]
        return jax.device_get(gathered)

    def _swap_in_scatter(self, cache, payloads: List[Dict], idx, slot, w):
        """One donated program for the whole swap-in restore: every
        layer's payload scattered into its freshly allocated pool blocks
        (``idx``; sentinel pads drop) plus the slot's per-layer lengths /
        position rebuilt at ``w`` written tokens. Donating ``cache`` lets
        XLA rewrite the pools in place — the per-leaf ``.at[].set`` path
        this replaces materialized a second copy of every pool leaf."""
        li = 0
        segments = []
        for seg in cache["segments"]:
            new_seg = {}
            for lk in sorted(seg, key=int):
                sa = dict(seg[lk]["self"])
                pay = payloads[li]
                li += 1
                for k in _POOL_KEYS:
                    sa[k] = sa[k].at[:, idx].set(pay[k], mode="drop")
                sa["length"] = sa["length"].at[:, slot].set(w)
                new_seg[lk] = {"self": sa}
            segments.append(new_seg)
        return {"segments": segments,
                "position": cache["position"].at[slot].set(w),
                "block_tbl": cache["block_tbl"]}

    def _scatter_blocks(self, slot: int, ids, payload: List[Dict],
                        w: int) -> None:
        """Restore swapped payloads into freshly allocated pool blocks via
        the jitted donated scatter. The pad bucket (power of two) bounds
        compile variants across restores of different block counts."""
        m = len(ids)
        m_pad = _pow2_ceil(max(m, 1))
        idx = np.full((m_pad,), self.num_blocks, np.int32)   # pad: dropped
        idx[:m] = ids
        pad = m_pad - m

        def padded(a):
            if not pad:
                return jnp.asarray(a)
            widths = ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)
            return jnp.asarray(np.pad(a, widths))

        payloads = [{k: padded(pay[k]) for k in _POOL_KEYS}
                    for pay in payload]
        self.state["cache"] = self._swap_in_jit(
            self.state["cache"], payloads, jnp.asarray(idx),
            jnp.int32(slot), jnp.int32(w))

    def _swap_out(self, slot: int) -> None:
        """Preempt ``slot``: gather its quantized blocks into a host
        buffer (int8 payloads move 4x cheaper than an fp32 cache would),
        release the blocks to the pool, and park the request on the swap
        queue for later restore. Works for decode residents and for the
        in-progress chunk job (which resumes from its last finished
        window)."""
        with self.trace.span("swap_out", slot=slot) as sp:
            job = next((j for j in self._tail_jobs if j["slot"] == slot),
                       None)
            w = job["c0"] if job is not None else self._written[slot]
            # only blocks holding written tokens travel; lazily grown tail
            # blocks past ``w`` hold nothing and are re-allocated on restore
            ids = self.alloc.owned(slot)[:self.alloc.blocks_for_tokens(w)]
            payload = self._gather_blocks(ids)
            nbytes = sum(a.nbytes for layer in payload
                         for a in layer.values())
            if job is not None:
                # the affinity key rides along so a restored tail job keeps
                # its chain "hot" for queued sharers
                rec = {"req": job["req"], "kind": "prefill", "w": w,
                       "akey": job.get("akey")}
                self._tail_jobs.remove(job)
            else:
                req = self._slot_req.pop(slot)
                self._written.pop(slot)
                # the live sampling key travels with the record so restore
                # resumes the slot's PRNG state verbatim. Today the key is
                # constant per slot (steps derive their keys by folding
                # n_gen into it), so rebuilding from
                # fold_in(PRNGKey(seed), uid) happened to match — carrying
                # it makes the invariant explicit instead of leaning on
                # that coincidence, and any future key-advancing sampler
                # keeps resume bit-exact.
                n_gen, out_row, last, key = jax.device_get(
                    (self.state["n_gen"][slot], self.state["out"][slot],
                     self.state["tokens"][slot, 0],
                     self.state["keys"][slot]))
                rec = {"req": req, "kind": "decode", "w": w,
                       "n_gen": int(n_gen), "out": np.asarray(out_row),
                       "last": int(last), "key": np.asarray(key)}
                self.state["active"] = \
                    self.state["active"].at[slot].set(False)
                # tokens decoded before preemption stream out now (the out
                # row is already on the host); the stream resumes at the
                # next harvest after restore — same tokens, same order
                self._emit_stream(req,
                                  rec["out"][req._streamed:rec["n_gen"]],
                                  done=False)
            rec["payload"] = payload
            rec["bytes"] = nbytes
            self.alloc.release(slot)
            self._admit_seq.pop(slot, None)
            self._tbl_dirty = True
            self._swapped.append(rec)
            self._host["preemptions"] += 1
            self._host["swap_out_bytes"] += nbytes
        self._host["swap_s"] += sp.dt
        self.trace.event("preempted", uid=rec["req"].uid,
                         kind=rec["kind"], bytes=nbytes)

    def _try_swap_in(self) -> None:
        """Restore swapped-out requests while slots and blocks allow.

        Policy — strictly FCFS over the swap queue, head-of-line: a
        later, smaller record is never restored ahead of the head even
        when it would fit right now and free a slot sooner. The head was
        already preempted once; letting smaller records jump the queue
        could starve it indefinitely behind a stream of short work, so
        fairness wins over pool utilization here (the cost is idle blocks
        while the head's worst case doesn't fit). The per-record gate is
        the request's full remaining worst case — a restore that could
        immediately become the next victim would thrash swap bandwidth
        for no progress.

        Every stop condition below is terminal for this call, so the free
        list is gathered once up front and popped as restores consume
        slots instead of being rebuilt per iteration."""
        free = self._free_slots()
        while self._swapped:
            rec = self._swapped[0]
            req = rec["req"]
            if rec["kind"] == "prefill" \
                    and len(self._tail_jobs) >= self.tail_batch:
                return
            if not free:
                return
            need = len(req.prompt) + req.max_new_tokens - 1
            if self.alloc.blocks_for_tokens(need) > self.alloc.free_blocks:
                return              # head doesn't fit: nobody jumps it
            self._restore(free.pop(0), rec)
            self._swapped.pop(0)
            self._note_residency()

    def _restore(self, slot: int, rec: Dict) -> None:
        """Swap a preempted request back in: fresh blocks, scattered
        payload, and the slot's sampling/output state rebuilt exactly as
        it was — greedy AND sampled decode resume bit-identically (the
        record carries the slot's PRNG key verbatim; see ``_swap_out``)."""
        with self.trace.span("swap_in", slot=slot,
                             kind=rec["kind"]) as sp:
            self._restore_body(slot, rec)
        self._host["swap_in_bytes"] += rec["bytes"]
        self._host["swap_s"] += sp.dt
        self.trace.event("swap_resumed", uid=rec["req"].uid,
                         kind=rec["kind"], bytes=rec["bytes"])

    def _restore_body(self, slot: int, rec: Dict) -> None:
        req, w = rec["req"], rec["w"]
        need = len(req.prompt) + req.max_new_tokens - 1
        if self.admission == "reserve":
            # preemption only triggers under optimistic admission, but a
            # reserve-mode restore must re-debit to stay accounted
            if not self.alloc.reserve(slot, need):
                raise RuntimeError("swap-in gate admitted an unreservable "
                                   "request — accounting bug")
        else:
            self.alloc.register(slot)
        self.alloc.ensure(slot, w)
        self._tbl_dirty = True
        ids = self.alloc.owned(slot)
        self._scatter_blocks(slot, ids, rec["payload"], w)
        self._admit_seq[slot] = self._seq
        self._seq += 1
        if rec["kind"] == "prefill":
            self._tail_jobs.append({"req": req, "slot": slot, "c0": w,
                                    "akey": rec.get("akey")})
        else:
            st = self.state
            keys = jnp.asarray(rec["key"])
            st["tokens"] = st["tokens"].at[slot, 0].set(rec["last"])
            st["out"] = st["out"].at[slot].set(jnp.asarray(rec["out"]))
            st["n_gen"] = st["n_gen"].at[slot].set(rec["n_gen"])
            st["active"] = st["active"].at[slot].set(True)
            st["eos"] = st["eos"].at[slot].set(req.eos_id)
            st["max_new"] = st["max_new"].at[slot].set(req.max_new_tokens)
            st["temp"] = st["temp"].at[slot].set(req.temperature)
            st["top_k"] = st["top_k"].at[slot].set(req.top_k)
            st["keys"] = st["keys"].at[slot].set(keys)
            self._slot_req[slot] = req
            self._written[slot] = w
            if self.spec is not None:
                # rebuild the draft cache from the consumed stream
                # (prompt + generated-so-far): swap records never carry
                # draft payloads
                consumed = np.concatenate(
                    [np.asarray(req.prompt, np.int32),
                     np.asarray(rec["out"][:rec["n_gen"] - 1], np.int32)])
                self._draft_prefill_rows([(slot, consumed)])

    # ------------------------------------------------------------------
    # Speculative decoding: host driver
    # ------------------------------------------------------------------

    def _draft_prefill_rows(self, rows) -> None:
        """Prefill the draft cache for freshly armed decode residents.

        ``rows``: (slot, consumed-token array) pairs — the prompt at
        admission / tail completion, or prompt + generated-so-far on a
        swap-in restore (the draft cache never travels with a swap
        record; it is rebuilt from tokens, which keeps swap bytes
        unchanged and the draft strictly a performance hint)."""
        if self.spec is None or not rows:
            return
        n = len(rows)
        n_pad = min(_pow2_ceil(n), self.slots)
        lens = np.ones((n_pad,), np.int32)
        lens[:n] = [len(t) for _, t in rows]
        L = -(-int(lens.max()) // self.prefill_bucket) * self.prefill_bucket
        toks = np.zeros((n_pad, L), np.int32)
        slot_idx = np.full((n_pad,), self.slots, np.int32)   # pad: dropped
        for i, (s, t) in enumerate(rows):
            toks[i, :len(t)] = t
            slot_idx[i] = s
        self._draft_cache = self._draft_admit_jit(
            self.draft_params, self._draft_cache, jnp.asarray(toks),
            jnp.asarray(lens), jnp.asarray(slot_idx))
        self._host["spec_draft_prefill_tokens"] += int(
            sum(len(t) for _, t in rows))

    def _spec_step(self) -> None:
        """One speculative wave over every decode resident.

        The draft proposes ``k`` tokens per slot (one compiled scan of
        the cheap model), the target verifies all residents' windows in
        ONE compiled call (``_spec_wave``), the accepted prefix plus one
        target token commit, and the rejected suffix rolls back — the
        wave re-clamps the device counters, this driver releases the
        whole blocks past each survivor's accepted extent
        (``BlockAllocator.trim``). Capacity/COW for the full window is
        secured up front exactly like a decode chunk, so preemption and
        prefix-shared (COW) blocks compose with the wave unchanged.
        """
        C = self.spec.k + 1
        tail = np.zeros((self.slots,), np.int32)
        hb_need = 1
        with self.trace.span("schedule", kind="spec"):
            for s in list(self._slot_req):
                if s not in self._slot_req:
                    continue        # preempted by an earlier iteration
                r = self._slot_req[s]
                w = self._written[s]
                # the window is clamped to the row's remaining max_new
                # budget, so peak occupancy never exceeds the
                # admission-time worst case (prompt + max_new - 1) — no
                # spec headroom
                t = min(C, len(r.prompt) + r.max_new_tokens - 1 - w)
                if not self._ensure(s, w + t):
                    continue        # s itself was swapped out
                if s not in self._slot_req \
                        or not self._cow_guard(s, w, w + t):
                    continue
                tail[s] = t
                hb_need = max(hb_need, self.alloc.blocks_for_tokens(w + t))
            for s in range(self.slots):
                # a slot whose capacity was secured and then swapped out
                # by a LATER iteration's preemption must ride the wave
                # fully masked (its table row is already parked on the
                # sentinel)
                if tail[s] and s not in self._slot_req:
                    tail[s] = 0
        if not self._slot_req:
            return
        if not tail.any():
            # no slot has budget to draft — every resident finished at
            # admission (max_new == 1); they still need harvesting or
            # they would sit in their slots forever
            self._harvest()
            return
        self._push_tables()
        greedy_only = all(r.temperature <= 0.0
                          for r in self._slot_req.values())
        n_gen_before = {s: self._written[s] - len(r.prompt) + 1
                        for s, r in self._slot_req.items()}
        st = self.state
        with self.trace.span("spec_draft", rows=len(self._slot_req)):
            dtoks, dq, self._draft_cache = self._draft_jit(
                self.draft_params, self._draft_cache, st["tokens"],
                st["temp"], st["top_k"], st["keys"], st["n_gen"],
                st["cache"]["position"], greedy_only)
        with self.trace.span("spec_verify"):
            hb = min(_pow2_ceil(hb_need), self.table_len)
            self.state = self._spec_jit(self.params, self.state, dtoks, dq,
                                        jnp.asarray(tail), hb, greedy_only)
            # ONE host sync per wave (like a decode chunk): the harvest's
            # (active, n_gen) fetch also yields each row's committed count
            with self.trace.span("sync"):
                act, n_gen = jax.device_get((self.state["active"],
                                             self.state["n_gen"]))
        drafted = accepted = 0
        for s, n0 in n_gen_before.items():
            m_s = int(n_gen[s]) - n0
            if m_s > 0:
                # rows committing nothing were inactive the whole wave
                # (finished at admission, e.g. EOS on the first token) —
                # their proposals were never in play, so counting them
                # as drafted(-and-rolled-back) or letting their m = 0
                # subtract from the accepted total would corrupt the
                # accept rate the CI gate watches
                drafted += max(int(tail[s]) - 1, 0)
                accepted += m_s - 1
        self._host["spec_waves"] += 1
        self._host["spec_drafted"] += drafted
        self._host["spec_accepted"] += accepted
        self._host["spec_rolled_back"] += drafted - accepted
        self._harvest(act, n_gen)
        # rollback, host side: finished slots were fully released by the
        # harvest; survivors drop the whole blocks past their accepted
        # extent (freshly grown for this wave, so never shared/indexed)
        for s in list(self._slot_req):
            if self.alloc.trim(s, self._written[s]):
                self._tbl_dirty = True

    def _harvest(self, act=None, n_gen=None) -> None:
        """Admission-boundary sync: pull finished slots' token buffers.
        ``act``/``n_gen`` may be passed pre-fetched (the spec step pulls
        them for its acceptance accounting) to keep one sync per step."""
        if not self._slot_req:
            return
        with self.trace.span("harvest"):
            self._harvest_body(act, n_gen)

    def _harvest_body(self, act, n_gen) -> None:
        if act is None:
            with self.trace.span("sync"):
                act, n_gen = jax.device_get((self.state["active"],
                                             self.state["n_gen"]))
        if self._paged:
            # exact per-slot progress from the device counter: each decode
            # step writes the KV of the token it consumes, so a slot holds
            # prompt + (n_gen - 1) written tokens (the newest sampled token
            # is not yet committed). Advancing by a flat ``decode_block``
            # instead over-counts any slot that did not run the full chunk
            # (armed by a tail wave or restored mid-window while others
            # kept the loop alive) — and an over-counted ``_written`` makes
            # a later swap-out gather unwritten tail blocks as payload.
            for s, r in self._slot_req.items():
                if act[s]:
                    self._written[s] = len(r.prompt) + int(n_gen[s]) - 1
        finished = [s for s in self._slot_req if not act[s]]
        # incremental token drain: streaming residents surface the tokens
        # decoded since the last harvest (decode_block / spec-wave
        # granularity) without waiting for finish — their rows ride the
        # same batched device_get as the finished slots' buffers
        streaming = [s for s, r in self._slot_req.items()
                     if act[s] and r.on_tokens is not None
                     and int(n_gen[s]) > r._streamed]
        fetch = finished + streaming
        if not fetch:
            return
        with self.trace.span("sync", rows=len(fetch)):
            all_rows = jax.device_get(
                self.state["out"][np.asarray(fetch)])
        rows = all_rows[:len(finished)]
        for i, s in enumerate(streaming):
            r = self._slot_req[s]
            self._emit_stream(r, all_rows[len(finished) + i,
                                          r._streamed:int(n_gen[s])],
                              done=False)
        for i, s in enumerate(finished):
            req = self._slot_req.pop(s)
            req.generated = rows[i, :n_gen[s]].tolist()
            req.done = True
            self._emit_stream(req, req.generated[req._streamed:], done=True)
            self.scheduler.on_finished(req)
            tm = getattr(req, "_timing", None)
            if tm is not None and tm.admit_t is not None \
                    and tm.finish_t is not None:
                self.metrics.observe_finished(
                    tm.latency, tm.finish_t - tm.admit_t,
                    len(req.generated))
            if self._paged:
                if self.prefix_cache and req.generated:
                    # content-address the decoded stream too (the last
                    # sampled token is never written): a follow-up prompt
                    # extending prompt+completion — a chat turn, say —
                    # reuses these blocks. [0, true_w) is intact even for
                    # an early-EOS slot: its post-EOS masked steps only
                    # rewrote positions >= true_w.
                    true_w = len(req.prompt) + int(n_gen[s]) - 1
                    content = np.concatenate(
                        [np.asarray(req.prompt, np.int32),
                         np.asarray(req.generated[:-1], np.int32)])
                    self.alloc.register_prefix(s, content, true_w)
                self.alloc.release(s)       # blocks return to the pool
                self._written.pop(s, None)
                self._admit_seq.pop(s, None)
                self._tbl_dirty = True      # row parked on the sentinel

    # ------------------------------------------------------------------
    # Drive
    # ------------------------------------------------------------------

    def step(self) -> None:
        """One admission + one batched tail-wave window of the in-progress
        tail/chunked admissions + one decode round (a speculative
        draft+verify wave when spec is enabled, else one on-device decode
        chunk) + harvest."""
        self._step_idx += 1
        self.trace.step = self._step_idx
        with self.trace.span("step"):
            with self.trace.span("admit"):
                self._admit()
            if self._tail_jobs:
                self._advance_tail_jobs()
            if self._slot_req:
                with self.trace.span("decode") as sp:
                    if self.spec is not None:
                        self._spec_step()  # drafts + verify + harvest+trim
                    else:
                        greedy_only = all(r.temperature <= 0.0
                                          for r in self._slot_req.values())
                        if self._paged:
                            with self.trace.span("schedule", kind="decode"):
                                self._ensure_decode_blocks()
                        with self.trace.span("decode_chunk",
                                             rows=len(self._slot_req)):
                            self.state = self._decode_jit(
                                self.params, self.state, greedy_only)
                        # the harvest's device_get doubles as the sync
                        self._harvest()
                self._host["decode_s"] += sp.dt
                self._host["decode_rounds"] += 1
                self._note_rate("_pred_round_s", sp.dt)

    def _flush_partial(self) -> None:
        """Surface still-resident slots' tokens (budget-aborted drain):
        their buffers are on device and already counted in the stats.
        Swapped-out requests surface the tokens captured at preemption."""
        for rec in self._swapped:
            if rec["kind"] == "decode":
                rec["req"].generated = rec["out"][:rec["n_gen"]].tolist()
        if not self._slot_req:
            return
        resident = sorted(self._slot_req)
        n_gen = jax.device_get(self.state["n_gen"])
        rows = jax.device_get(self.state["out"][np.asarray(resident)])
        for i, s in enumerate(resident):
            self._slot_req[s].generated = rows[i, :n_gen[s]].tolist()

    def run_until_drained(self, max_steps: int = 10_000) -> Dict:
        """Serve until queue + slots are empty; ``max_steps`` bounds the
        total decode-step budget (chunk-granular). If the budget aborts the
        drain, in-flight requests keep their partial ``generated`` output
        (``done`` stays False)."""
        chunks = 0
        while ((self.scheduler.pending or self._slot_req
                or self._tail_jobs or self._swapped)
               and chunks * self.decode_block < max_steps):
            self.step()
            chunks += 1
        self._flush_partial()
        return self.stats()

    # ------------------------------------------------------------------
    # decode_block auto-tuning
    # ------------------------------------------------------------------

    def _probe_state(self) -> Dict:
        """Fresh state with every slot armed to run a full decode chunk."""
        st = self._blank_state()
        st["active"] = jnp.ones((self.slots,), bool)
        st["max_new"] = jnp.full((self.slots,), self.max_new_cap, jnp.int32)
        return self._shard_state(st)

    def _probe_decode_block(self, candidates=(4, 8, 16, 32)) -> int:
        """Measured decode-step latency probe (``decode_block="auto"``).

        Times one compiled decode chunk at lengths 1 and 8 to split the
        per-chunk cost into a fixed part (dispatch + the host sync that
        follows every chunk) and a per-step part, then picks the smallest
        candidate whose amortized fixed cost is under 15% of compute —
        bigger chunks waste steps on slots that finish mid-chunk, so we
        want the smallest chunk that the host overhead can afford.
        Passing an int ``decode_block`` to the constructor overrides this.
        """
        def chunk_time(c: int) -> float:
            self.decode_block = c
            # donate each probe state: the probe must not stack extra full
            # cache pytrees on top of the engine's own state (the paged
            # pool can be sized near device HBM)
            fn = self._under_mesh(
                jax.jit(self._decode_chunk, static_argnums=(2,),
                        donate_argnums=(1,)))
            jax.block_until_ready(
                fn(self.params, self._probe_state(), True)["tokens"])
            best = float("inf")
            for _ in range(3):          # min-of-N: shed host scheduler noise
                st = self._probe_state()
                t0 = time.perf_counter()
                jax.block_until_ready(fn(self.params, st, True)["tokens"])
                best = min(best, time.perf_counter() - t0)
            return best

        t1 = chunk_time(1)
        t8 = chunk_time(8)
        per_step = max((t8 - t1) / 7.0, 1e-9)
        overhead = max(t1 - per_step, 0.0)
        for c in candidates:
            if overhead <= 0.15 * c * per_step:
                return c
        return candidates[-1]

    # ------------------------------------------------------------------
    # Stats
    # ------------------------------------------------------------------

    def stats(self) -> Dict:
        """Serving counters and latency stats (one host sync).

        Every key, so bench parsers don't reverse-engineer them:

        ==========================  =========================================
        key                         meaning
        ==========================  =========================================
        tokens_out                  tokens returned to requests (first
                                    prefill token + committed decode tokens)
        decode_steps                device decode steps executed
        decode_s / decode_step_s    wall seconds in decode / per device step
        decode_rounds               engine steps that ran a decode chunk or
                                    spec wave (the shed predictor's divisor)
        prefill_calls               compiled prefill/tail-finish admissions
        prefill_chunks              tail-wave rows advanced (batched chunks)
        prompt_tokens_prefilled     prompt tokens actually computed (excludes
                                    prefix-cache hits)
        prefill_s                   wall seconds in prefill + tail waves
        prefix_hit_tokens           prompt tokens served from the prefix
                                    cache instead of being prefilled
        prefix_lookups/_hit_blocks  prefix-index probes / whole blocks hit
        prefix_cache_blocks         evictable blocks alive only in the index
        prefix_evictions            indexed blocks reclaimed by allocation
        cow_copies                  copy-on-write block clones
        preemptions                 swap-outs (optimistic admission)
        swap_out_bytes/_in_bytes    quantized bytes moved by swaps
        swap_s                      wall seconds in swap gather/restore
        max_residents               peak concurrently resident requests
        pending_requests            requests waiting in the scheduler queue
        resident_requests           requests resident in slots (decode +
                                    in-flight tail prefills)
        swapped_requests            preempted requests awaiting restore
        free_blocks                 free cache blocks in the paged pool
        pool_occupancy              fraction of pool blocks in use
        cache_tokens_capacity       pool/stripe capacity in tokens
        peak_cache_tokens/_bytes    peak occupancy in tokens / bytes
        cache_bytes                 total cache allocation
        decode_block(_mode)         chunk length and how it was chosen
                                    ("fixed" / "auto" / "spec")
        mesh_shape / tp_degree      serving mesh axis sizes (None off-mesh)
                                    and the "model"-axis TP degree
        per_device_pool_bytes       one device's share of the KV cache
                                    (sharded leaves count shard bytes)
        per_device_weight_bytes     one device's share of the served
                                    weights (w4a8: the packed planes)
        weights_layout              serve weight layout ("bf16" / "w4a8")
        packed_weight_bytes         int4-packed weight + scale + bias bytes
                                    the w4a8 forward streams (0 under bf16)
        weight_hbm_saved_bytes      bf16 weight bytes per forward the packed
                                    layout no longer reads (0 under bf16)
        spec_waves/_drafted/        verify-waves run, draft tokens proposed
        _accepted/_rolled_back      / accepted / rolled back (spec only)
        spec_accept_rate            accepted / drafted (spec only)
        spec_k/_draft_layers/       the resolved SpecConfig actually
        _accept_mode                serving (spec only)
        requests_finished           requests fully served
        requests_shed               requests rejected by SLO shed-load
        requests_downgraded         requests demoted to best-effort
        ttft_p50_s/p95_s            submit -> first-token percentiles
        latency_p50_s/p95_s         submit -> finish percentiles
        ==========================  =========================================

        Paged-only keys appear only with ``kv_layout="paged"``; spec-only
        keys only when ``spec`` is configured.

        Every value is a native Python scalar / container — the dict
        round-trips through ``json.dumps`` unchanged, which is what the
        ``/v1/stats`` and ``/v1/metrics`` HTTP surfaces serve.
        """
        steps, committed = jax.device_get((self.state["steps"],
                                           self.state["committed"]))
        d = dict(self._host)
        prefill_tokens = d.pop("prefill_tokens")
        d["prompt_tokens_prefilled"] = d.pop("prompt_tokens")
        d["decode_steps"] = int(steps)
        d["tokens_out"] = int(committed) + prefill_tokens
        d["decode_step_s"] = (d["decode_s"] / max(int(steps), 1))
        d["max_residents"] = self._max_residents
        d["decode_block"] = self.decode_block
        d["decode_block_mode"] = self._decode_block_mode
        d["mesh_shape"] = (dict(self.mesh.shape)
                           if self.mesh is not None else None)
        d["tp_degree"] = self.tp
        d["per_device_pool_bytes"] = _device_local_bytes(
            self.state["cache"]["segments"])
        d["per_device_weight_bytes"] = _device_local_bytes(
            self._served_weight_leaves())
        d["weights_layout"] = self.weights_layout
        d["packed_weight_bytes"] = self._w4a8_bytes["packed"]
        d["weight_hbm_saved_bytes"] = max(
            self._w4a8_bytes["replaced"] - self._w4a8_bytes["packed"], 0)
        if self.spec is not None:
            drafted = d["spec_drafted"]
            d["spec_accept_rate"] = (d["spec_accepted"] / drafted
                                     if drafted else 0.0)
            d["spec_k"] = self.spec.k
            d["spec_draft_layers"] = self.spec.resolved_layers(self.cfg)
            d["spec_accept_mode"] = self.spec.accept_mode
        d["pending_requests"] = self.scheduler.pending
        d["resident_requests"] = (len(self._slot_req)
                                  + len(self._tail_jobs))
        d["swapped_requests"] = len(self._swapped)
        if self._paged:
            d["prefix_lookups"] = self.alloc.prefix_lookups
            d["prefix_hit_blocks"] = self.alloc.prefix_hit_blocks
            d["prefix_cache_blocks"] = self.alloc.cached_blocks
            d["prefix_evictions"] = self.alloc.prefix_evictions
            d["free_blocks"] = self.alloc.free_blocks
            d["pool_occupancy"] = (1.0 - self.alloc.free_blocks
                                   / max(self.num_blocks, 1))
            cap_tokens = self.num_blocks * self.block_size
            d["cache_tokens_capacity"] = cap_tokens
            d["peak_cache_tokens"] = self.alloc.peak_blocks * self.block_size
        else:
            cap_tokens = self.slots * self.cache_len
            d["cache_tokens_capacity"] = cap_tokens
            # a dense stripe is reserved whole for a slot's lifetime:
            # reservation *is* usage, fragmentation included — but only
            # for the stripes that were actually occupied at peak
            d["peak_cache_tokens"] = self._max_residents * self.cache_len
        d["cache_bytes"] = self._cache_bytes
        d["peak_cache_bytes"] = int(
            self._cache_bytes * d["peak_cache_tokens"] / max(cap_tokens, 1))
        d["compile_variants"] = self.compile_variant_counts()
        d.update(self.scheduler.stats())
        return _jsonable(d)

    # ------------------------------------------------------------------
    # Compiled-graph introspection (the `repro.analysis` audit surface)
    # ------------------------------------------------------------------

    def compile_variant_counts(self) -> Dict[str, int]:
        """Live compiled-variant count per wave family — fresh compiles
        observed through the ``_wave`` registry since construction. The
        retrace-budget audit and operators read the same numbers."""
        return {f: len(v) for f, v in self._wave_variants.items()}

    def wave_variant_signatures(self) -> Dict[str, List[str]]:
        """Per-family shape signatures of every call that compiled a new
        variant, in compile order — names the offending shape when a
        family blows its retrace budget."""
        return {f: list(v) for f, v in self._wave_variants.items()}

    def pool_shard_elems(self) -> int:
        """Per-device element count of the largest int8 cache plane —
        the reference size for the dequant-placement audit (a wholesale
        dequant materializes at least one full plane in floats)."""
        best = 0
        for leaf in jax.tree.leaves(self.state["cache"]):
            if leaf.dtype != jnp.int8:
                continue
            sh = getattr(leaf, "sharding", None)
            if sh is not None and hasattr(sh, "shard_shape"):
                n = int(np.prod(sh.shard_shape(leaf.shape)))
            else:
                n = int(leaf.size)
            best = max(best, n)
        return best

    def compiled_waves(self, buckets: int = 1) -> List[Dict]:
        """Enumerate every live wave family as an auditable unit.

        Each entry is a plain dict (no analysis import here — the
        auditor duck-types engines):

          family   — registry name ("decode", "admit_paged", ...)
          label    — family plus the representative statics
          lower    — zero-arg closure returning the ``jax.jit(...).lower``
                     of one representative call, built from
                     ``ShapeDtypeStruct``s that mirror the live arrays
                     (shapes, dtypes, shardings) — nothing materializes
          donated  — leaf inventory of the donated argument(s):
                     [{path, dtype, bytes}] with per-device byte counts,
                     so the donation rule can name a leaked plane

        ``buckets`` enumerates that many power-of-two prefill length
        buckets (L = prefill_bucket * 2**b) for the admission families.
        Fresh jit objects are lowered, so the serving jits' compile
        caches — and ``compile_variant_counts`` — are untouched.
        """
        def sds(tree):
            return jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=getattr(a, "sharding", None)),
                tree)

        def arr(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        def inventory(tree) -> List[Dict]:
            flat, _ = jax.tree_util.tree_flatten_with_path(tree)
            out = []
            for path, a in flat:
                sh = getattr(a, "sharding", None)
                if sh is not None and hasattr(sh, "shard_shape"):
                    n = int(np.prod(sh.shard_shape(a.shape)))
                else:
                    n = int(np.prod(a.shape))
                dt = np.dtype(a.dtype)
                out.append({"path": jax.tree_util.keystr(path),
                            "dtype": dt.name, "bytes": n * dt.itemsize})
            return out

        params = sds(self.params)
        state = sds(self.state)
        cache = state["cache"]
        S = self.slots
        mesh = self.mesh
        waves: List[Dict] = []

        def add(family, fn, args, *, static_argnums=(), donate_argnums=(),
                label=None):
            jitted = jax.jit(fn, static_argnums=static_argnums,
                             donate_argnums=donate_argnums)

            def lower(jitted=jitted, args=args):
                if mesh is not None:
                    with mesh:
                        return jitted.lower(*args)
                return jitted.lower(*args)

            donated: List[Dict] = []
            for dn in donate_argnums:
                donated += inventory(args[dn])
            waves.append({"family": family, "label": label or family,
                          "lower": lower, "donated": donated})

        add("decode", self._decode_chunk, (params, state, False),
            static_argnums=(2,), donate_argnums=(1,),
            label="decode[greedy=False]")
        for b in range(max(buckets, 1)):
            L = self.prefill_bucket * (1 << b)
            n_pad = min(_pow2_ceil(S), S)
            common = (arr((n_pad, L), jnp.int32), arr((n_pad,), jnp.int32),
                      arr((n_pad,), jnp.int32))
            tail = (arr((n_pad,), jnp.int32), arr((n_pad,), jnp.int32),
                    arr((n_pad,), jnp.float32), arr((n_pad,), jnp.int32),
                    arr((n_pad, 2), jnp.uint32), False)
            if self._paged:
                nb = self.alloc.blocks_for_tokens(L)
                add("admit_paged", self._admit_batch_paged,
                    (params, state, *common, arr((n_pad, nb), jnp.int32),
                     *tail),
                    static_argnums=(11,), donate_argnums=(1,),
                    label=f"admit_paged[n={n_pad},L={L}]")
            else:
                add("admit_dense", self._admit_batch,
                    (params, state, *common, *tail),
                    static_argnums=(10,), donate_argnums=(1,),
                    label=f"admit_dense[n={n_pad},L={L}]")
        if self._paged:
            C = self.prefill_chunk
            hb = min(_pow2_ceil(self.alloc.blocks_for_tokens(C)),
                     self.table_len)
            add("tail", self._tail_wave,
                (params, cache, arr((1, C), jnp.int32),
                 arr((1,), jnp.int32), arr((1,), jnp.int32),
                 arr((1,), jnp.int32), hb),
                static_argnums=(6,), donate_argnums=(1,),
                label=f"tail[rows=1,C={C},hb={hb}]")
            payloads = []
            for layer in self._attn_layer_caches():
                pay = {}
                for k in _POOL_KEYS:
                    shape = list(layer["self"][k].shape)
                    shape[1] = 1            # m_pad=1 restored blocks
                    pay[k] = arr(tuple(shape), layer["self"][k].dtype)
                payloads.append(pay)
            add("swap_in", self._swap_in_scatter,
                (cache, payloads, arr((1,), jnp.int32),
                 arr((), jnp.int32), arr((), jnp.int32)),
                donate_argnums=(0,), label="swap_in[m=1]")
            add("cow", self._cow_copy,
                (cache, arr((1,), jnp.int32), arr((1,), jnp.int32)),
                donate_argnums=(0,), label="cow[n=1]")
        if self.spec is not None:
            dparams = sds(self.draft_params)
            dcache = sds(self._draft_cache)
            k = self.spec.k
            add("spec_draft", self._spec_draft,
                (dparams, dcache, arr((S, 1), jnp.int32),
                 arr((S,), jnp.float32), arr((S,), jnp.int32),
                 arr((S, 2), jnp.uint32), arr((S,), jnp.int32),
                 arr((S,), jnp.int32), False),
                static_argnums=(8,), donate_argnums=(1,),
                label="spec_draft[greedy=False]")
            dq = (arr((S, k, self.cfg.vocab_size), jnp.float32)
                  if self.spec.accept_mode == "rejection" else None)
            hb = min(_pow2_ceil(self.alloc.blocks_for_tokens(
                self.max_seq_len)), self.table_len)
            add("spec_verify", self._spec_wave,
                (params, state, arr((S, k), jnp.int32), dq,
                 arr((S,), jnp.int32), hb, False),
                static_argnums=(5, 6), donate_argnums=(1,),
                label=f"spec_verify[hb={hb},greedy=False]")
            n_pad = min(_pow2_ceil(S), S)
            L = self.prefill_bucket
            add("admit_draft", self._draft_admit,
                (dparams, dcache, arr((n_pad, L), jnp.int32),
                 arr((n_pad,), jnp.int32), arr((n_pad,), jnp.int32)),
                donate_argnums=(1,), label=f"admit_draft[n={n_pad},L={L}]")
        return waves
