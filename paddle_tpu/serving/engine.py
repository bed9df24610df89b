"""Prefill/decode generation engine over the static-shape KV cache.

Up to four :class:`~paddle_tpu.jit.functionalize.CompiledStep` programs:

* ``serve_prefill`` — one request's prompt, padded to a length bucket,
  runs causally and writes its K/V into the request's batch slot. One
  executable per bucket (telemetry ``compile[serve_prefill]`` == buckets
  touched), because the bucket width is the ONLY shape that varies — the
  prompt length, slot index and position are traced scalars.
* ``serve_prefill_chunk`` (when ``prefill_chunk`` is set) — ONE fixed-size
  chunk of one prompt, written at a traced ``(slot, offset)``. A long
  prompt becomes ``ceil(n / chunk)`` dispatches the scheduler interleaves
  with decode ticks, so admitting a long prompt no longer stalls active
  streams for its full prefill. Compiles exactly once: chunk width is the
  only shape and it is fixed.
* ``serve_decode`` — ONE token per batch slot, every slot at its own
  position. All shapes are fixed at ``[max_batch, 1]`` + the cache
  buffers, so this compiles exactly once and its per-step cost is O(1)
  in generated length.
* ``serve_verify`` (when ``spec_k > 0``) — the speculative-decoding
  verifier: ``[max_batch, spec_k + 1]`` tokens (each slot's last
  committed token + k draft tokens) in ONE forward. Because batched
  decode on this class of model is weight-bandwidth-bound, verifying
  k+1 positions costs roughly one decode tick; every accepted draft is
  a decode tick saved. The step returns the verifier's own greedy
  argmax at every window position — acceptance and commitment happen
  host-side (:meth:`GenerationEngine.verify_once` +
  :meth:`GenerationEngine.commit_lengths`), which is what makes the
  committed stream byte-identical to plain greedy decode.

Sampling (temperature / top-k / top-p) rides the decode and verify steps
as per-slot TRACED arrays (``keys/temps/top_ks/top_ps``): changing a
request's sampling params changes data, never shapes, so the
``retrace-*`` lint rules stay clean and the compile counters stay
bounded. Greedy remains the default (all temps 0) and the whole sampled
branch sits behind one ``lax.cond`` so pure-greedy batches skip it.

All steps thread the model through ``stateful=[model]`` (weights donated
state, aliased in place) and the cache through ``donate_inputs`` so the
``dynamic_update_slice`` writes recycle the cache HBM instead of copying
it — reusing the donation machinery the training pipeline built
(``jit/functionalize.py``, ``io.DeviceLoader`` contract: a donated batch
is consumed; the engine rebinds its cache reference after every call).

Also here: :class:`EncoderScorer`, the bucketed compile-once-per-bucket
serving path for encoder models (BERT sequence scoring).
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from ..fault import inject as _inject
from ..framework.tensor import Tensor
from ..jit.functionalize import CompiledStep
from ..nn.functional import LengthMask
from ..profiler import telemetry as _telemetry
from ..profiler import tracing as _tracing
from .kv_cache import (
    MASK_MIN,
    ChunkView,
    CountsView,
    DecodeView,
    KVCache,
    PrefillView,
    RingPrefillView,
    StateDecodeView,
    StatePrefillView,
    _leaf,
    cache_route,
    count_cache_route,
    default_buckets,
    pick_bucket,
)

__all__ = ["GenerationEngine", "EncoderScorer", "RecurrentStateError",
           "RingCacheError"]


class RecurrentStateError(ValueError):
    """The model keeps recurrent state per slot and the engine was asked for
    a step that would have to rewind or resume it: speculative verify
    (rejected drafts have already advanced the state) or chunked prefill
    (a later chunk continues from the state the last one left, while decode
    ticks in between advance the same slot)."""


class RingCacheError(ValueError):
    """The model keeps some layers' K/V as a ring of their window and the
    engine was asked for a step that needs the ring's order: speculative
    verify (a rejected draft has already overwritten the row ``window``
    positions back) or chunked prefill (a later chunk's first queries need
    rows that its own writes replace)."""


def _sample_next(logits, keys, temps, top_ks, top_ps):
    """Per-slot next-token selection over ``[batch, vocab]`` logits.

    Greedy slots (``temps[i] == 0``) take the argmax; sampled slots draw
    from the temperature-scaled distribution after top-k/top-p
    filtering, each slot under its OWN threefry key (streams are
    independent per slot and deterministic per seed). The sampled branch
    sits behind ``lax.cond`` so an all-greedy batch pays only the argmax
    — and because the branch predicate is DATA, flipping a request to
    sampling never recompiles.

    Keys advance by one split per call for every slot, sampled or not,
    so a slot's stream depends only on (seed, ticks since seeding) —
    the determinism the seeded-sampling tests pin down.

    Returns ``(next_tok int32 [batch], new_keys uint32 [batch, 2])``.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    new_keys = jax.vmap(lambda k: jax.random.split(k, 1)[0])(keys)

    def _sampled(ops):
        lg, ks, t, tk, tp = ops
        vocab = lg.shape[-1]
        scaled = lg.astype(jnp.float32) / jnp.maximum(t, 1e-6)[:, None]
        # top-k: keep logits >= the k-th largest (sorted-descending
        # threshold at index k-1); top_k == 0 disables
        desc = -jnp.sort(-scaled, axis=-1)
        k_idx = jnp.clip(tk - 1, 0, vocab - 1)
        k_thresh = jnp.take_along_axis(desc, k_idx[:, None], axis=-1)
        keep = jnp.where((tk > 0)[:, None], scaled >= k_thresh, True)
        # top-p: smallest prefix of the sorted distribution with
        # cumulative probability >= top_p (exclusive-cumsum < top_p keeps
        # at least the head token); top_p == 1 disables
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        cnt = jnp.maximum(
            ((cum - probs) < tp[:, None]).astype(jnp.int32).sum(-1), 1)
        p_thresh = jnp.take_along_axis(desc, (cnt - 1)[:, None], axis=-1)
        keep = keep & jnp.where((tp < 1.0)[:, None],
                                scaled >= p_thresh, True)
        filt = jnp.where(keep, scaled, MASK_MIN)
        return jax.vmap(jax.random.categorical)(ks, filt).astype(jnp.int32)

    sampled = jax.lax.cond(
        jnp.any(temps > 0.0), _sampled, lambda ops: greedy,
        (logits, keys, temps, top_ks, top_ps))
    return jnp.where(temps > 0.0, sampled, greedy), new_keys


class GenerationEngine:
    """Serve a decoder-only LM with O(1) static-shape decode. The model is
    callable as ``model(ids, position_ids=, attn_mask=, cache=) -> (logits,
    cache)``, has a ``cfg`` with ``max_position_embeddings``, and DECLARES
    what a slot keeps: ``model.cache_spec()`` lists, layer by layer, K/V
    rows, fixed-shape recurrent state, or nothing (``kv_cache.KVCache``).
    The engine allocates that, hands every layer its view of it in each
    step (``cache=`` is the list of views) and threads the whole through
    the steps' donation. Counts a layer notes on its view (an expert
    layer's routing) come back with the step's tokens, in the same
    read-back, and are filed in the serving tick's record.

    Args:
        model: the language model; switched to ``eval()``.
        max_batch: decode batch width == concurrent request slots.
        max_len: cache capacity per slot (prompt + generated tokens);
            defaults to, and may not exceed, the model's position table.
        prefill_buckets: prompt pad widths; defaults to powers of two up
            to ``max_len``. One prefill compile per bucket ever touched.
        cache_dtype: K/V buffer dtype; defaults to the dtype the model
            declares (bf16 weights → bf16 cache).
        freeze_weights: fold the weights into the compiled executables as
            constants instead of threading them as (donated) state.
            ``"auto"`` (default) freezes on the CPU backend only —
            measured on XLA:CPU, gemm against an ARGUMENT weight repacks
            the whole matrix every call (a batch≥2 gpt2-124M decode step:
            ~500 ms vs ~120 ms frozen; batch-1 takes the gemv path and
            never repacks), while constants are packed once at compile.
            On TPU the trade flips: constants are duplicated into every
            per-bucket executable (the ``hbm-const-folded`` lint hazard),
            so weights stay threaded state there. A frozen engine
            snapshots the weights at compile — rebuild it after updating
            the model.
        spec_k: speculative-decoding draft window — build the
            ``serve_verify`` step over ``[max_batch, spec_k + 1]``
            windows. 0 (default) builds no verifier; the scheduler
            falls back to plain one-token decode.
        prefill_chunk: chunked-prefill width — build the
            ``serve_prefill_chunk`` step. None (default) keeps prefill
            one-shot-per-bucket only. Prompts whose padded chunk count
            would overrun ``max_len`` (see :meth:`chunked_prefill_fits`)
            fall back to the bucketed one-shot path.

    A model with recurrent state takes neither ``spec_k`` nor
    ``prefill_chunk``: :class:`RecurrentStateError`; nor does one that
    keeps a ring (a ``kv`` entry with a ``window`` under ``max_len``):
    :class:`RingCacheError`.
    """

    def __init__(self, model, *, max_batch=8, max_len=None,
                 prefill_buckets=None, cache_dtype=None,
                 freeze_weights="auto", spec_k=0, prefill_chunk=None):
        cfg = model.cfg
        model.eval()
        self.model = model
        self.max_batch = int(max_batch)
        self.max_len = int(max_len or cfg.max_position_embeddings)
        if self.max_len > cfg.max_position_embeddings:
            raise ValueError(
                f"max_len={self.max_len} exceeds the model's position "
                f"table ({cfg.max_position_embeddings})")
        self.prefill_buckets = tuple(sorted(
            int(b) for b in (prefill_buckets
                             or default_buckets(self.max_len))))
        if self.prefill_buckets[-1] > self.max_len:
            raise ValueError(
                f"prefill bucket {self.prefill_buckets[-1]} exceeds "
                f"max_len={self.max_len}")
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if self.spec_k and self.spec_k + 1 > self.max_len:
            raise ValueError(
                f"spec_k={self.spec_k} needs a [*, {self.spec_k + 1}] "
                f"verify window but max_len is {self.max_len}")
        self.prefill_chunk = (None if prefill_chunk is None
                              else int(prefill_chunk))
        if self.prefill_chunk is not None and not (
                1 <= self.prefill_chunk <= self.max_len):
            raise ValueError(
                f"prefill_chunk={prefill_chunk} outside "
                f"[1, max_len={self.max_len}]")
        self.cache_spec = list(model.cache_spec())
        kinds = [layer["kind"] if layer else None
                 for layer in self.cache_spec]
        self.has_state = "state" in kinds
        if self.has_state and (self.spec_k or self.prefill_chunk):
            raise RecurrentStateError(
                f"the model keeps recurrent state in {kinds.count('state')} "
                f"layers: spec_k={self.spec_k} would have to roll it back "
                f"after a rejected draft and prefill_chunk="
                f"{self.prefill_chunk} to resume it across ticks; neither "
                f"is built")
        #: how each ``kv`` entry is kept (``full`` / ``ring``), None for the
        #: other entries; and the rings' windows
        self._routes = [cache_route(layer, self.max_len)
                        if kind == "kv" else None
                        for layer, kind in zip(self.cache_spec, kinds)]
        self.ring_windows = sorted({
            int(layer["window"]) for layer, route
            in zip(self.cache_spec, self._routes) if route == "ring"})
        if self.ring_windows and (self.spec_k or self.prefill_chunk):
            raise RingCacheError(
                f"the model keeps {self._routes.count('ring')} layers' K/V "
                f"as rings of {self.ring_windows} rows: spec_k={self.spec_k} "
                f"would have to restore the rows a rejected draft overwrote "
                f"and prefill_chunk={self.prefill_chunk} to read rows its "
                f"own chunk replaces; neither is built")
        #: names of the counts the model's layers note, in order
        self.count_names = next(
            (tuple(layer["names"]) for layer in self.cache_spec
             if layer and layer["kind"] == "counts"), ())
        self.cache_dtype = None if cache_dtype is None \
            else jnp.dtype(cache_dtype)
        self.cache = self._alloc_cache()
        #: slots that hold a request (prefilled, not yet released): the
        #: decode and verify steps are handed this mask, a slot outside it
        #: has no valid key for their attention and keeps its length, and
        #: its tokens do not count as routed nor its state as live
        self._live = np.zeros((self.max_batch,), bool)
        #: the host's copy of each slot's length (rows its next decode step
        #: reads, the new one included): the tick record's live rows
        self._rows = np.zeros((self.max_batch,), np.int64)
        if freeze_weights == "auto":
            freeze_weights = jax.default_backend() == "cpu"
        self.freeze_weights = bool(freeze_weights)
        self._footprints = None  # predicted_footprints() cache
        # per-slot sampling state: DATA threaded through the compiled
        # steps (shapes fixed at [max_batch]), never compile-time consts
        self._keys = jnp.stack(
            [jax.random.PRNGKey(i) for i in range(self.max_batch)])
        self._temps = np.zeros((self.max_batch,), np.float32)
        self._top_ks = np.zeros((self.max_batch,), np.int32)
        self._top_ps = np.ones((self.max_batch,), np.float32)
        stateful = [] if self.freeze_weights else [model]
        self._prefill_step = CompiledStep(
            self._make_prefill(), stateful=stateful, donate_state=True,
            donate_inputs=["args[3]"])
        self._decode_step = CompiledStep(
            self._make_decode(), stateful=stateful, donate_state=True,
            donate_inputs=["args[1]"])
        self._verify_step = None
        if self.spec_k:
            self._verify_step = CompiledStep(
                self._make_verify(), stateful=stateful, donate_state=True,
                donate_inputs=["args[1]"])
        self._chunk_step = None
        if self.prefill_chunk:
            self._chunk_step = CompiledStep(
                self._make_chunk_prefill(), stateful=stateful,
                donate_state=True, donate_inputs=["args[4]"])

    # -- the declared cache --------------------------------------------------
    def _alloc_cache(self):
        return KVCache.from_spec(self.cache_spec, self.max_batch,
                                 self.max_len, self.cache_dtype)

    def _views(self, cache, kv, state, valid, ring=None):
        """One view a layer, by what the layer declared: ``kv(k, v)`` and
        ``state(arrays)`` build this step's views of K/V and of recurrent
        state, ``ring(k, v, window)`` of K/V kept as a ring, a counting
        layer is told which tokens are ``valid``, a layer that keeps nothing
        gets None."""
        views = []
        for layer, route, k, v, st in zip(self.cache_spec, self._routes,
                                          cache.ks, cache.vs, cache.states):
            kind = layer["kind"] if layer else None
            views.append(ring(k, v, int(layer["window"])) if route == "ring"
                         else kv(k, v) if kind == "kv"
                         else state(st) if kind == "state"
                         else CountsView(valid) if kind == "counts"
                         else None)
        return views

    @staticmethod
    def _collect(views, lengths):
        """The next cache from the step's views, and the sum of what the
        counting layers noted (None without one)."""
        counts = [v.counts for v in views if isinstance(v, CountsView)]
        cache = KVCache(
            [getattr(v, "k", None) for v in views],
            [getattr(v, "v", None) for v in views], lengths,
            [getattr(v, "arrays", None) for v in views])
        return cache, (sum(counts[1:], counts[0]) if counts else None)

    @staticmethod
    def _pack(tokens, counts):
        """The step's tokens and, behind them, its layers' counts: ONE int32
        array, so the counts cost no read-back of their own."""
        if counts is None:
            return Tensor(tokens)
        return Tensor(jnp.concatenate([tokens.reshape(-1), counts]))

    def _unpack(self, packed, n_tokens, live_slots=None, prefilled=None):
        """Host side of :meth:`_pack`: the tokens; the counts are filed in
        the open serving tick's record (``Telemetry.add_count``), a decode
        step's under the layers' names, a prefill's under ``<name>.prefill``
        (``live_slots`` is given by decode alone, ``prefilled = (bucket,
        prompt tokens)`` by the one-shot prefill)."""
        out = np.asarray(_leaf(packed)).reshape(-1)
        if _telemetry.enabled():
            tm = _telemetry.get_telemetry()
            suffix = ".prefill" if live_slots is None else ""
            for name, n in zip(self.count_names, out[n_tokens:]):
                tm.add_count(name + suffix, int(n))
            if prefilled is not None:
                # the keys a ring layer's queries saw (each its window's),
                # by bucket: the bucket decides which attention route ran
                bucket, n = prefilled
                for w in self.ring_windows:
                    m = min(n, w)
                    tm.add_count(f"serve.ring_live_rows.prefill.b{bucket}",
                                 m * (m + 1) // 2 + (n - m) * w)
            if live_slots is not None:
                # of max_batch slots, those the step's attention visited
                tm.add_count("serve.decode_live_slots", int(live_slots))
                if self.has_state:
                    tm.add_count("serve.state_live_slots", int(live_slots))
                # the K/V rows the step's attention had to read: of a
                # full-length layer, of a ring (one layer of each)
                rows = self._rows[self._live]
                tm.add_count("serve.kv_live_rows", int(rows.sum()))
                for w in self.ring_windows:
                    tm.add_count("serve.ring_live_rows",
                                 int(np.minimum(rows, w).sum()))
        return out[:n_tokens]

    def release_slot(self, slot):
        """The scheduler's word that ``slot`` holds no request any more:
        the decode step's attention stops reading its rows, its length stops
        advancing, its tokens stop counting as routed, its state as live."""
        self._live[int(slot)] = False

    # -- traced step bodies --------------------------------------------------
    def _make_prefill(self):
        model = self.model
        max_len = self.max_len

        def serve_prefill(tokens, length, slot, cache):
            # tokens [1, bucket] int32; length/slot traced 0-d int32
            ln = _leaf(length).astype(jnp.int32)
            sl = _leaf(slot).astype(jnp.int32)
            bucket = tokens.shape[1]
            i = jnp.arange(bucket, dtype=jnp.int32)
            # causal within the chunk AND key < prompt length: padded tail
            # queries produce garbage logits which are never read (the last
            # valid position is sliced out below). The LengthMask carries
            # (q_pos, kv_len) so the blockwise/Pallas attention paths never
            # materialize the [1, 1, bucket, bucket] score mask.
            lmask = LengthMask(i[None, :], ln[None])
            # one mask a layer KIND: a ring's layer sees its band of the
            # bucket (and leaves the prompt's last rows in the ring)
            bands = {w: LengthMask(i[None, :], ln[None], window=w)
                     for w in self.ring_windows}
            views = self._views(
                cache, lambda k, v: PrefillView(k, v, sl),
                lambda st: StatePrefillView(st, sl, ln), (i < ln)[None, :],
                lambda k, v, w: RingPrefillView(k, v, sl, ln, bands[w]))
            logits, views = model(
                tokens, position_ids=Tensor(i[None, :]),
                attn_mask=lmask, cache=views)
            lv = _leaf(logits)  # [1, bucket, vocab]
            # next-token logits live at the last VALID position, not the
            # padded chunk end — a traced dynamic_slice keeps it shape-stable
            last = jax.lax.dynamic_slice(
                lv, (jnp.int32(0), ln - 1, jnp.int32(0)),
                (1, 1, lv.shape[-1]))[0, 0]
            next_tok = jnp.argmax(last).astype(jnp.int32)
            new_len = jax.lax.dynamic_update_slice(
                _leaf(cache.lengths), jnp.minimum(ln, max_len)[None], (sl,))
            new_cache, counts = self._collect(views, new_len)
            return self._pack(next_tok, counts), new_cache

        return serve_prefill

    def _make_chunk_prefill(self):
        model = self.model
        max_len = self.max_len

        def serve_prefill_chunk(tokens, chunk_len, off, slot, cache):
            # tokens [1, chunk] int32; chunk_len/off/slot traced 0-d int32.
            # Chunk queries sit at absolute positions off..off+chunk-1 and
            # attend over the slot's FULL row (earlier chunks included):
            # ChunkView returns the row, the mask admits keys j <= off + i.
            cl = _leaf(chunk_len).astype(jnp.int32)
            of = _leaf(off).astype(jnp.int32)
            sl = _leaf(slot).astype(jnp.int32)
            chunk = tokens.shape[1]
            i = jnp.arange(chunk, dtype=jnp.int32)
            pos = of + i
            # key j is valid for chunk row i iff j <= of + i — exactly the
            # LengthMask q_pos semantics over the slot's full cached row
            lmask = LengthMask(pos[None, :])
            views = self._views(
                cache, lambda k, v: ChunkView(k, v, sl, of), None,
                (i < cl)[None, :])
            logits, views = model(
                tokens, position_ids=Tensor(pos[None, :]),
                attn_mask=lmask, cache=views)
            lv = _leaf(logits)  # [1, chunk, vocab]
            # only meaningful on the FINAL chunk (the host reads it then);
            # padded tail queries beyond chunk_len produce garbage logits
            # never read — same contract as serve_prefill
            last = jax.lax.dynamic_slice(
                lv, (jnp.int32(0), cl - 1, jnp.int32(0)),
                (1, 1, lv.shape[-1]))[0, 0]
            next_tok = jnp.argmax(last).astype(jnp.int32)
            new_len = jax.lax.dynamic_update_slice(
                _leaf(cache.lengths),
                jnp.minimum(of + cl, max_len)[None], (sl,))
            new_cache, counts = self._collect(views, new_len)
            return self._pack(next_tok, counts), new_cache

        return serve_prefill_chunk

    def _make_decode(self):
        model = self.model
        max_len = self.max_len

        def serve_decode(tokens, cache, keys, temps, top_ks, top_ps, live):
            # tokens [max_batch, 1] int32 — each slot's last token, fed at
            # that slot's own position; shapes NEVER vary step to step.
            # ``live [max_batch]`` bool: the slots that hold a request
            ln = _leaf(cache.lengths).astype(jnp.int32)
            lv = _leaf(live)
            pos = jnp.minimum(ln, max_len - 1)  # [b]
            # each slot's single query row sits at its own position; keys
            # j <= pos[b] are valid — no [b, 1, 1, max_len] mask tensor. A
            # slot without a request has no valid key: its attention reads
            # nothing of the cache and gives zeros
            lmask = LengthMask(jnp.where(lv, pos, -1)[:, None])
            # a ring holds position p in row p mod window: after this
            # step's write its first min(pos + 1, window) rows are the
            # window's keys, in an order softmax does not care about
            rings = {w: LengthMask(jnp.where(
                lv, jnp.minimum(pos, w - 1), -1)[:, None])
                for w in self.ring_windows}

            def full(k, v):
                count_cache_route("full")
                return DecodeView(k, v, pos, live=lv)

            def ring(k, v, w):
                count_cache_route("ring")
                return DecodeView(k, v, pos % w, rings[w])

            views = self._views(cache, full, StateDecodeView, lv[:, None],
                                ring)
            logits, views = model(
                tokens, position_ids=Tensor(pos[:, None]),
                attn_mask=lmask, cache=views)
            last = _leaf(logits)[:, -1]  # [b, vocab]
            # token selection ON DEVICE: only [b] int32 (+ the rotated
            # keys) crosses back to the host, never the [b, vocab] logits
            next_tok, new_keys = _sample_next(
                last, _leaf(keys), _leaf(temps),
                _leaf(top_ks), _leaf(top_ps))
            # a slot without a request keeps its length (and so the row
            # its write lands on, which the next prefill starts over)
            new_cache, counts = self._collect(
                views, Tensor(ln + lv.astype(jnp.int32)))
            return self._pack(next_tok, counts), Tensor(new_keys), new_cache

        return serve_decode

    def _make_verify(self):
        model = self.model
        max_len = self.max_len
        W = self.spec_k + 1

        def serve_verify(tokens, cache, keys, temps, top_ks, top_ps,
                         live):
            # tokens [max_batch, W] int32 — window = [last committed
            # token, k drafts]; each slot's window sits at its OWN
            # positions ln..ln+W-1. K/V for all W positions are written
            # by this step (DecodeView multi-row write), so the accepted
            # prefix is already cached when the host commits lengths;
            # rejected positions sit beyond the committed length =
            # garbage-by-contract, masked until overwritten.
            ln = _leaf(cache.lengths).astype(jnp.int32)
            # the scheduler guarantees ln + W <= max_len for LIVE slots
            # (headroom fallback to plain decode otherwise); the clamp
            # only ever moves dead slots, whose rows nobody reads
            pos0 = jnp.minimum(ln, max_len - W)  # [b]
            offs = jnp.arange(W, dtype=jnp.int32)
            pos = pos0[:, None] + offs[None, :]  # [b, W]
            # window row i of slot b queries position pos[b, i]; keys
            # j <= pos[b, i] are valid — no [b, 1, W, max_len] mask tensor;
            # a slot without a request has no valid key, as in serve_decode
            lmask = LengthMask(jnp.where(_leaf(live)[:, None], pos, -1))
            views = self._views(
                cache, lambda k, v: DecodeView(k, v, pos0, live=_leaf(live)),
                None, None)
            logits, views = model(
                tokens, position_ids=Tensor(pos),
                attn_mask=lmask, cache=views)
            lv = _leaf(logits).astype(jnp.float32)  # [b, W, vocab]
            # greedy[b, i] = the verifier's own next token GIVEN the
            # window prefix up to i — the host accepts the longest draft
            # prefix matching it, then emits greedy[b, a] itself, which
            # is exactly what plain greedy decode would have produced
            greedy = jnp.argmax(lv, axis=-1).astype(jnp.int32)
            # sampled slots never speculate: their committed token is the
            # window-position-0 draw (same logits a plain tick sees)
            tok0, new_keys = _sample_next(
                lv[:, 0], _leaf(keys), _leaf(temps),
                _leaf(top_ks), _leaf(top_ps))
            # lengths UNCHANGED — the host commits the accepted count
            # (commit_lengths) after comparing drafts to greedy
            new_cache, _ = self._collect(views, Tensor(ln))
            return (Tensor(greedy), Tensor(tok0), Tensor(new_keys),
                    new_cache)

        return serve_verify

    # -- host-side API -------------------------------------------------------
    def _declare_variants(self):
        """(Re-)declare each serving step's legitimate executable count
        with telemetry so ``recompile_count`` stays a clean contract
        metric (0 = nothing retraced beyond the declared bucketing).
        Re-declared on every dispatch because ``telemetry.reset()`` swaps
        the Telemetry instance — the cost is a dict max under a lock."""
        if not _telemetry.enabled():
            return
        tm = _telemetry.get_telemetry()
        tm.declare_variants("serve_prefill", len(self.prefill_buckets))
        tm.declare_variants("serve_decode", 1)
        if self._verify_step is not None:
            tm.declare_variants("serve_verify", 1)
        if self._chunk_step is not None:
            tm.declare_variants("serve_prefill_chunk", 1)

    def set_slot_sampling(self, slot, *, temperature=0.0, top_k=0,
                          top_p=1.0, seed=0):
        """Arm sampling for a batch slot: temperature scaling with
        optional top-k / top-p (nucleus) filtering, seeded per request.
        All four are DATA in fixed ``[max_batch]`` arrays threaded
        through the compiled steps — arming/clearing a slot never
        recompiles. ``temperature=0`` keeps the slot greedy."""
        s = int(slot)
        if not (0 <= s < self.max_batch):
            raise ValueError(f"slot {slot} outside [0, {self.max_batch})")
        if temperature < 0 or not (0.0 < top_p <= 1.0) or top_k < 0:
            raise ValueError(
                f"bad sampling params: temperature={temperature} "
                f"top_k={top_k} top_p={top_p}")
        self._temps[s] = float(temperature)
        self._top_ks[s] = int(top_k)
        self._top_ps[s] = float(top_p)
        self._keys = self._keys.at[s].set(jax.random.PRNGKey(int(seed)))

    def clear_slot_sampling(self, slot):
        """Return a slot to greedy decoding (the default)."""
        s = int(slot)
        self._temps[s] = 0.0
        self._top_ks[s] = 0
        self._top_ps[s] = 1.0

    def slot_is_sampled(self, slot):
        return bool(self._temps[int(slot)] > 0.0)

    def prefill(self, slot, prompt_ids):
        """Prefill ``prompt_ids`` into batch slot ``slot``; returns the
        greedy next token (host int). Host↔device: one tiny token readback
        per request — the batched decode loop carries the heavy traffic."""
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if prompt.size >= self.max_len:
            raise ValueError(
                f"prompt of {prompt.size} tokens leaves no room to "
                f"generate within max_len={self.max_len}")
        if not (0 <= int(slot) < self.max_batch):
            raise ValueError(f"slot {slot} outside [0, {self.max_batch})")
        bucket = pick_bucket(prompt.size, self.prefill_buckets)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :prompt.size] = prompt
        self._declare_variants()
        # fault-injection point BEFORE the compiled call: the cache rides
        # donate_inputs, so a fault raised here leaves it un-donated and
        # the scheduler's retry runs against valid buffers
        _inject.check("serve.prefill")
        # the phase nests under the caller's context (a scheduler's
        # per-request prefill span, a tick's serve.admit); the compiled
        # step's dispatch — or compile, on a cold bucket — lands inside it
        with _telemetry.phase_span(
                "serve.prefill_dispatch",
                attrs={"slot": int(slot), "bucket": bucket,
                       "prompt_tokens": int(prompt.size)}):
            tok, cache = self._prefill_step(
                toks, np.int32(prompt.size), np.int32(slot), self.cache)
        self.cache = cache  # donated: the old buffers are consumed
        self._live[int(slot)] = True
        self._rows[int(slot)] = min(prompt.size + 1, self.max_len)
        with _telemetry.phase_span("serve.prefill_readback"):
            return int(self._unpack(
                tok, 1, prefilled=(bucket, int(prompt.size)))[0])

    def chunked_prefill_fits(self, prompt_len):
        """True when a prompt of this length can prefill through the
        chunked step: every chunk write (final one included, PADDED to
        the chunk width) must land inside ``max_len`` — XLA clamps an
        overhanging ``dynamic_update_slice``, which would silently stomp
        valid rows. Callers fall back to the bucketed one-shot prefill
        when this is False."""
        if self.prefill_chunk is None:
            return False
        c = self.prefill_chunk
        n = int(prompt_len)
        return n > 0 and c * ((n + c - 1) // c) <= self.max_len

    def prefill_chunk_step(self, slot, prompt_ids, off):
        """Run ONE prefill chunk: prompt tokens ``off .. off+chunk`` into
        slot ``slot``. Returns the greedy next token (host int) when this
        chunk completed the prompt, else None — callers re-enter with
        ``off + prefill_chunk`` next tick. The cache length advances to
        the chunk end as a side effect, so decode/verify garbage writes
        at the partial slot stay above the valid region and are
        overwritten by the next chunk."""
        if self._chunk_step is None:
            raise RuntimeError(
                "engine was built without prefill_chunk; pass "
                "prefill_chunk= to GenerationEngine to enable chunked "
                "prefill")
        prompt = np.asarray(prompt_ids, np.int32).reshape(-1)
        c = self.prefill_chunk
        off = int(off)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if prompt.size >= self.max_len:
            raise ValueError(
                f"prompt of {prompt.size} tokens leaves no room to "
                f"generate within max_len={self.max_len}")
        if not (0 <= int(slot) < self.max_batch):
            raise ValueError(f"slot {slot} outside [0, {self.max_batch})")
        if off % c or not (0 <= off < prompt.size):
            raise ValueError(
                f"chunk offset {off} not a multiple of {c} inside the "
                f"{prompt.size}-token prompt")
        if off + c > self.max_len:
            raise ValueError(
                f"chunk [{off}, {off + c}) overruns max_len="
                f"{self.max_len}; gate on chunked_prefill_fits()")
        piece = prompt[off:off + c]
        toks = np.zeros((1, c), np.int32)
        toks[0, :piece.size] = piece
        self._declare_variants()
        _inject.check("serve.prefill")  # pre-donation: retry-safe
        with _telemetry.phase_span(
                "serve.prefill_dispatch",
                attrs={"slot": int(slot), "off": off,
                       "chunk_tokens": int(piece.size),
                       "prompt_tokens": int(prompt.size)}):
            tok, cache = self._chunk_step(
                toks, np.int32(piece.size), np.int32(off), np.int32(slot),
                self.cache)
        self.cache = cache
        if off + piece.size >= prompt.size:
            self._live[int(slot)] = True
            self._rows[int(slot)] = min(prompt.size + 1, self.max_len)
            with _telemetry.phase_span("serve.prefill_readback"):
                return int(self._unpack(tok, 1)[0])
        return None

    def decode_once(self, last_tokens):
        """One batched decode step: ``last_tokens[b]`` is each slot's most
        recent token. Returns the next token per slot (np int32 [b])."""
        # the phase is everything the host does to hand the step over
        with _telemetry.phase_span("serve.decode_dispatch"):
            feed = np.asarray(last_tokens, np.int32).reshape(
                self.max_batch, 1)
            self._declare_variants()
            _inject.check("serve.decode")  # pre-donation: retry-safe
            tok, keys, cache = self._decode_step(
                feed, self.cache, self._keys, self._temps,
                self._top_ks, self._top_ps, self._live.copy())
            self.cache = cache
            self._keys = _leaf(keys)
        # the one blocking wait of a tick: the host sits here while the
        # device runs the step it was just handed
        with _telemetry.phase_span("serve.decode_readback"):
            out = self._unpack(tok, self.max_batch, self._live.sum())
        self._rows[self._live] = np.minimum(self._rows[self._live] + 1,
                                            self.max_len)
        return out

    def verify_once(self, window_tokens):
        """One speculative verify step over ``[max_batch, spec_k + 1]``
        windows (``window[b, 0]`` = slot b's last committed token,
        ``window[b, 1:]`` = draft tokens; pad unused lanes with 0).

        Returns ``(greedy [b, W] int32, tok0 [b] int32)`` numpy:
        ``greedy[b, i]`` is the verifier's next token given the window
        prefix through i (the host's acceptance comparison), ``tok0[b]``
        the sampled/greedy committed token at window position 0 for
        slots that don't speculate. Cache lengths are NOT advanced —
        call :meth:`commit_lengths` with the per-slot accepted counts."""
        if self._verify_step is None:
            raise RuntimeError(
                "engine was built with spec_k=0; pass spec_k= to "
                "GenerationEngine to enable speculative decoding")
        w = self.spec_k + 1
        feed = np.asarray(window_tokens, np.int32).reshape(
            self.max_batch, w)
        self._declare_variants()
        _inject.check("serve.verify")  # pre-donation: cache-safe on retry
        with _telemetry.phase_span("serve.verify_dispatch",
                                   attrs={"window": w}):
            greedy, tok0, keys, cache = self._verify_step(
                feed, self.cache, self._keys, self._temps,
                self._top_ks, self._top_ps, self._live.copy())
        self.cache = cache
        self._keys = _leaf(keys)
        with _telemetry.phase_span("serve.verify_readback"):
            return (np.asarray(_leaf(greedy)), np.asarray(_leaf(tok0)))

    def commit_lengths(self, advance):
        """Advance per-slot cached lengths by ``advance[b]`` tokens after
        host-side speculative acceptance. A tiny [max_batch] device add
        (no compiled-step dispatch, no readback): the K/V rows being
        committed were already written by the verify step."""
        adv = jnp.asarray(np.asarray(advance, np.int32)
                          .reshape(self.max_batch))
        ln = _leaf(self.cache.lengths).astype(jnp.int32)
        self.cache = KVCache(self.cache.ks, self.cache.vs,
                             jnp.minimum(ln + adv, self.max_len),
                             self.cache.states)
        self._rows = np.minimum(self._rows + np.asarray(advance, np.int64)
                                .reshape(self.max_batch), self.max_len)

    def generate(self, prompt_ids, max_new_tokens=32, eos_id=None):
        """Greedy single-request generation (slot 0; other slots idle).
        Per-step cost is O(1) in generated length: one ``serve_decode``
        dispatch, no recompiles, no cache copies."""
        with _tracing.span("generate",
                           attrs={"prompt_tokens": len(prompt_ids),
                                  "max_new_tokens": int(max_new_tokens)}):
            out = [self.prefill(0, prompt_ids)]
            while len(out) < int(max_new_tokens):
                if eos_id is not None and out[-1] == eos_id:
                    break
                feed = np.zeros((self.max_batch,), np.int32)
                feed[0] = out[-1]
                out.append(int(self.decode_once(feed)[0]))
        return out

    def lengths(self):
        """Per-slot cached-token counts (host numpy). A slot without a
        request keeps the count it was released with until the next prefill
        into it starts over."""
        return np.asarray(_leaf(self.cache.lengths))

    def predicted_footprints(self, refresh=False):
        """Predicted HBM footprints of this engine's serving programs,
        from the static memory-lint timeline (``analysis.analyze_memory``
        over the decode step — abstract, no device execution). Cached
        after the first call; ``refresh=True`` re-derives.

        Returns a dict:

        * ``decode_peak_bytes`` — predicted live-set peak of one batched
          ``serve_decode`` dispatch (cache + weights + activations).
          Fusion-aware since ISSUE 18: elementwise decode temporaries
          the :mod:`~paddle_tpu.analysis.fusion` plan certifies XLA
          elides are not priced, so admission headroom is no longer
          eaten by phantom activation bytes;
        * ``cache_bytes`` — the static KV cache allocation;
        * ``base_bytes`` — everything but the cache (weights, decode
          temps): resident whether or not any request is active;
        * ``per_token_bytes`` — KV bytes one cached token pins across
          all layers that keep ``max_len`` rows (a ring's rows are not a
          token's: they stop at the window);
        * ``prefill_bucket_bytes`` — per-bucket KV bytes a request
          padded to that bucket pins at admit, a ring's rows up to its
          window.

        When the abstract timeline is unavailable (lint failure),
        ``decode_peak_bytes`` falls back to plain cache arithmetic
        (``2 × cache_bytes`` — donation holds old+new cache at the swap)
        and ``timeline`` is None; the byte-based admission policy stays
        usable either way."""
        if self._footprints is not None and not refresh:
            return dict(self._footprints)
        cache_bytes = int(self.cache.nbytes())
        # what a cached token pins: a row of every full-length K/V entry,
        # with the rest of a slot (recurrent state) spread over its
        # positions as before; a ring's rows are priced apart, a row a
        # position up to its window
        per_ring_row = {w: 0 for w in self.ring_windows}
        for k, layer, route in zip(self.cache.ks, self.cache_spec,
                                   self._routes):
            if route == "ring":  # K and V of one row of one slot
                per_ring_row[int(layer["window"])] += 2 * int(
                    _leaf(k)[0, 0].size) * _leaf(k).dtype.itemsize
        ring_bytes = self.max_batch * sum(
            w * row for w, row in per_ring_row.items())
        per_token = max(1, (cache_bytes - ring_bytes)
                        // (self.max_batch * self.max_len))
        timeline = None
        try:
            from .. import analysis

            timeline = analysis.analyze_memory(
                self._decode_step, *self.example_decode_args([1]))
            decode_peak = float(timeline.peak_bytes)
        except Exception:  # noqa: BLE001 - advisory: fall back to arithmetic
            decode_peak = float(2 * cache_bytes)
        self._footprints = {
            "decode_peak_bytes": decode_peak,
            "cache_bytes": float(cache_bytes),
            "base_bytes": max(0.0, decode_peak - cache_bytes),
            "per_token_bytes": float(per_token),
            "prefill_bucket_bytes": {
                int(b): float(per_token * min(self.max_len, int(b)) + sum(
                    row * min(w, int(b)) for w, row in per_ring_row.items()))
                for b in self.prefill_buckets},
            "timeline": timeline,
        }
        return dict(self._footprints)

    @property
    def decode_step(self):
        """The compiled decode step — exposed for graph-lint
        (``analysis.lint_step(engine.decode_step, *example_args, ...)``)."""
        return self._decode_step

    @property
    def prefill_step(self):
        return self._prefill_step

    @property
    def verify_step(self):
        """The compiled speculative verify step (None when spec_k=0)."""
        return self._verify_step

    @property
    def chunk_step(self):
        """The compiled chunked-prefill step (None when disabled)."""
        return self._chunk_step

    def _example_sampling_args(self):
        return (np.zeros((self.max_batch, 2), np.uint32),
                np.zeros((self.max_batch,), np.float32),
                np.zeros((self.max_batch,), np.int32),
                np.ones((self.max_batch,), np.float32))

    def _example_cache(self, lengths):
        ln = np.zeros((self.max_batch,), np.int32)
        ln[:len(lengths)] = np.asarray(lengths, np.int32)
        cache = self._alloc_cache()
        return KVCache(cache.ks, cache.vs, jnp.asarray(ln), cache.states)

    def example_decode_args(self, lengths):
        """A shape-faithful ``(tokens, cache, keys, temps, top_ks,
        top_ps, live)`` example batch for static lint: fresh (non-donated)
        cache buffers with the given per-slot lengths. Two consecutive
        positions lint identically — that IS the O(1) contract the
        ``kv-cache-concat`` rule checks."""
        tokens = np.zeros((self.max_batch, 1), np.int32)
        return (tokens, self._example_cache(lengths),
                *self._example_sampling_args(),
                np.ones((self.max_batch,), bool))

    def example_verify_args(self, lengths):
        """Shape-faithful example batch for linting the speculative
        verify step — same contract as :meth:`example_decode_args` but
        with a ``[max_batch, spec_k + 1]`` token window."""
        if self._verify_step is None:
            raise RuntimeError("engine was built with spec_k=0")
        tokens = np.zeros((self.max_batch, self.spec_k + 1), np.int32)
        return (tokens, self._example_cache(lengths),
                *self._example_sampling_args(),
                np.ones((self.max_batch,), bool))

    def example_chunk_args(self, lengths, off=0):
        """Shape-faithful ``(tokens, chunk_len, off, slot, cache)``
        example batch for linting the chunked-prefill step — the config
        the long-context mem-lint zoo crosschecks (chunk queries against
        the full ``max_len`` cached row through the blockwise path)."""
        if self._chunk_step is None:
            raise RuntimeError("engine was built without prefill_chunk")
        tokens = np.zeros((1, self.prefill_chunk), np.int32)
        return (tokens, np.int32(self.prefill_chunk), np.int32(int(off)),
                np.int32(0), self._example_cache(lengths))


class EncoderScorer:
    """Bucketed batch scoring for encoder models (BERT classification).

    Pads requests to ``[max_batch, seq_bucket]`` so one ``serve_score``
    executable per sequence bucket serves every request mix — the serving
    analogue of the decoder engine's prefill bucketing (no KV cache:
    encoders are single-shot).
    """

    def __init__(self, model, *, max_batch=8, seq_buckets=None,
                 max_seq=None, freeze_weights="auto"):
        model.eval()
        self.model = model
        self.max_batch = int(max_batch)
        cfg = getattr(model, "cfg", None) or model.bert.cfg
        self.max_seq = int(max_seq or cfg.max_position_embeddings)
        self.seq_buckets = tuple(sorted(
            int(b) for b in (seq_buckets or default_buckets(self.max_seq))))
        if freeze_weights == "auto":  # same trade as GenerationEngine
            freeze_weights = jax.default_backend() == "cpu"
        self.freeze_weights = bool(freeze_weights)

        def serve_score(ids, mask):
            return model(ids, attention_mask=mask)

        self._step = CompiledStep(
            serve_score, stateful=[] if self.freeze_weights else [model],
            donate_state=True)

    def score(self, sequences):
        """Score a list of token-id sequences; returns ``[n, classes]``
        numpy logits. Requests are chunked to ``max_batch`` and padded to
        the smallest bucket that fits the chunk's longest sequence."""
        seqs = [np.asarray(s, np.int32).reshape(-1) for s in sequences]
        if _telemetry.enabled():
            _telemetry.get_telemetry().declare_variants(
                "serve_score", len(self.seq_buckets))
        outs = []
        for lo in range(0, len(seqs), self.max_batch):
            chunk = seqs[lo:lo + self.max_batch]
            bucket = pick_bucket(max(len(s) for s in chunk),
                                 self.seq_buckets)
            ids = np.zeros((self.max_batch, bucket), np.int32)
            mask = np.zeros((self.max_batch, bucket), np.float32)
            for i, s in enumerate(chunk):
                ids[i, :len(s)] = s
                mask[i, :len(s)] = 1.0
            logits = self._step(ids, mask)
            outs.append(np.asarray(_leaf(logits))[:len(chunk)])
        return np.concatenate(outs, axis=0)

    @property
    def step(self):
        return self._step
