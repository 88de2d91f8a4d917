"""Speculative decoding: prompt-lookup (n-gram) proposer + batched
on-device verify, mixed into the paged engine (which supplies the
verify programs and the page-room hooks ``_spec_room``,
``_spec_starved`` and ``_spec_can_fuse``).

Decode is HBM-bound: every generated token pays a full weight-stream
pass, so emitting ONE token per pass caps throughput at the
one-token-per-stream wall. Speculative decoding breaks it without a
draft model:

- **Propose** (host, numpy): match the last n-gram of each slot's
  prompt+generated history against its own earlier history and propose
  the ``k`` tokens that followed the most recent match (prompt-lookup
  decoding — free on repetitive/extractive text, harmless elsewhere).
  Pure host work; the serve loop runs it OUTSIDE the engine lock
  (``prepare_proposals`` — graftcheck rule GC108 enforces this).
- **Verify** (device, one program): one forward over the ``k+1``
  positions ``[t0, d1..dk]`` per slot — the nonzero-cache-offset
  prefill path from PR 1 — yields next-token logits at every position.
  Greedy rows accept the longest prefix of drafts matching the argmax;
  sampled rows rejection-sample against the filtered distribution and
  fall back to the verify model's own sample on first rejection, so
  the output distribution is exactly the non-speculative one.
- **Commit** (masked, fixed shapes): all ``k+1`` KV rows are computed;
  rows past each slot's accepted count scatter to a drop sentinel and
  the cache length advances by ``n_commit`` — per-slot variable
  acceptance never changes a program shape, so the jit key stays
  ``(k, sample, P)`` (the jaxpr audit gates on it).

Each verify round emits between 1 (no/failed proposals — a plain
decode step) and k+1 tokens per slot for one weight-stream pass.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import numpy as np


# --------------------------------------------------------------------------
# Host-side proposer (pure numpy — no device work, no locks required)
# --------------------------------------------------------------------------
def ngram_propose(hist, k: int, max_ngram: int = 3,
                  min_ngram: int = 1) -> np.ndarray:
    """Prompt-lookup proposal: match the trailing ``m``-gram of ``hist``
    (longest ``m`` first, ``max_ngram`` down to ``min_ngram``) against
    its earlier occurrences and return up to ``k`` tokens that followed
    the MOST RECENT match. ``hist`` is the slot's prompt + generated
    tokens, last element = the current (not yet cache-consumed) token.
    Returns an int32 array of length 0..k (empty = nothing to propose).
    O(len(hist) * max_ngram) numpy work — host-only by design."""
    n = len(hist)
    if k <= 0 or n < min_ngram + 1:
        return np.zeros((0,), np.int32)
    arr = np.asarray(hist, np.int64)
    for m in range(min(max_ngram, n - 1), min_ngram - 1, -1):
        pattern = arr[-m:]
        windows = np.lib.stride_tricks.sliding_window_view(arr, m)
        starts = np.nonzero((windows == pattern).all(axis=1))[0]
        # A usable match must have a continuation strictly before the
        # trailing n-gram itself.
        starts = starts[starts + m < n]
        if len(starts):
            begin = int(starts[-1]) + m
            return arr[begin:begin + k].astype(np.int32)
    return np.zeros((0,), np.int32)


def ngram_propose_device(hist, k: int, max_ngram: int = 3,
                         min_ngram: int = 1):
    """Device-side prompt-lookup proposal over a RIGHT-ALIGNED history
    window — the in-scan analogue of :func:`ngram_propose`, used by the
    fused speculative rounds (``_spec_step_fused``) where the host
    cannot see mid-scan commits to propose from.

    ``hist`` is ``[b, H]`` int32, left-padded with ``-1`` (token ids
    are non-negative, so pad never matches), last column = the current
    (not yet cache-consumed) token. Matching is limited to the window —
    matches the host proposer would find further back are missed, which
    only costs acceptance rate, never correctness (greedy commits are
    the verify model's own argmax regardless of what was proposed).

    Returns ``(proposals [b, k] int32, n_prop [b] int32)`` with
    positions past each row's ``n_prop`` zeroed. Runs INSIDE the
    engines' jitted fused-rounds programs (traced, fixed shapes)."""
    import jax.numpy as jnp

    b, H = hist.shape
    best_begin = jnp.zeros((b,), jnp.int32)
    best_found = jnp.zeros((b,), bool)
    for m in range(min(max_ngram, H - 1), min_ngram - 1, -1):
        gram = hist[:, H - m:]                               # [b, m]
        # Every window hist[:, p:p+m] as stacked static slices (m is
        # tiny and static, so this is a handful of cheap views).
        win = jnp.stack([hist[:, j:H - m + 1 + j]
                         for j in range(m)], axis=-1)        # [b, W, m]
        p_idx = jnp.arange(H - m + 1, dtype=jnp.int32)
        # Usable: full match, continuation strictly before the trailing
        # gram itself (p + m < H), window clear of the left pad.
        ok = (jnp.all(win == gram[:, None, :], axis=-1)
              & (p_idx[None, :] + m < H) & (win[:, :, 0] >= 0))
        p_best = jnp.max(jnp.where(ok, p_idx[None, :], -1), axis=1)
        found_m = p_best >= 0
        take = found_m & ~best_found
        best_begin = jnp.where(take, p_best + m, best_begin)
        best_found = best_found | found_m
    idx = jnp.clip(best_begin[:, None] + jnp.arange(k)[None, :],
                   0, H - 1)
    prop = jnp.take_along_axis(hist, idx, axis=1).astype(jnp.int32)
    n_prop = jnp.where(best_found, jnp.minimum(k, H - best_begin),
                       0).astype(jnp.int32)
    prop = jnp.where(jnp.arange(k)[None, :] < n_prop[:, None], prop, 0)
    return prop, n_prop


# --------------------------------------------------------------------------
# Device-side acceptance (the single-round and the fused verify programs)
# --------------------------------------------------------------------------
def verify_tokens(logits, proposals, n_prop, rng, temps, topks, topps,
                  *, sample: bool):
    """Batched draft acceptance. Runs INSIDE the engines' jitted verify
    programs.

    logits [b, k+1, vocab] fp32 — position ``i`` is the model's
    next-token distribution after consuming token ``i`` of
    ``[t0, d1..dk]``; proposals [b, k] int32; n_prop [b] valid drafts
    per row (padding positions always reject).

    Greedy rows (``temp <= 0`` or ``sample=False``): accept the longest
    draft prefix matching the per-position argmax; the token after the
    last accepted draft is the model's own argmax — byte-identical to
    vanilla greedy decode.

    Sampled rows: standard rejection sampling against the filtered
    (temperature/top-k/top-p) distribution. The proposer is a point
    mass, so draft ``d`` is accepted with probability ``p(d)`` and on
    first rejection the replacement is drawn from the residual
    ``p`` with ``d`` masked out — the committed stream is distributed
    exactly as non-speculative sampling.

    Returns ``(commit [b, k+1] int32, n_commit [b] int32)``:
    ``commit[:, :n_commit-1]`` are accepted drafts,
    ``commit[:, n_commit-1]`` is the verify model's own token
    (correction or bonus); 1 <= n_commit <= k+1."""
    import jax
    import jax.numpy as jnp

    from skypilot_tpu.models import llama

    b, k1, vocab = logits.shape
    k = k1 - 1
    greedy = jnp.argmax(logits, -1).astype(jnp.int32)        # [b, k+1]
    valid = jnp.arange(k)[None, :] < n_prop[:, None]         # [b, k]
    match = (proposals == greedy[:, :-1]) & valid
    if sample:
        masked = llama.filtered_logits(logits, temps[:, None],
                                       topks[:, None], topps[:, None])
        probs = jax.nn.softmax(masked, axis=-1)              # [b,k+1,v]
        rng_u, rng_c = jax.random.split(rng)
        p_draft = jnp.take_along_axis(
            probs[:, :k], proposals[..., None], axis=-1)[..., 0]
        u = jax.random.uniform(rng_u, (b, k))
        match = jnp.where(temps[:, None] > 0,
                          (u < p_draft) & valid, match)
    # Accepted prefix length a: drafts 1..a all passed.
    a = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    greedy_corr = jnp.take_along_axis(greedy, a[:, None], axis=1)[:, 0]
    if sample:
        # Replacement at position a: the rejected draft (when one was
        # actually rejected, a < n_prop) is masked from the filtered
        # distribution — the rejection-sampling residual for a
        # point-mass proposer.
        row = jnp.take_along_axis(masked, a[:, None, None], axis=1)[:, 0]
        rej = jnp.take_along_axis(
            jnp.concatenate([proposals, jnp.zeros((b, 1), jnp.int32)],
                            axis=1), a[:, None], axis=1)[:, 0]
        mask_rej = ((a < n_prop)[:, None]
                    & (jnp.arange(vocab)[None, :] == rej[:, None]))
        sampled_corr = jax.random.categorical(
            rng_c, jnp.where(mask_rej, -jnp.inf, row)).astype(jnp.int32)
        corr = jnp.where(temps > 0, sampled_corr, greedy_corr)
    else:
        corr = greedy_corr
    j = jnp.arange(k + 1)[None, :]
    padded = jnp.concatenate([proposals, jnp.zeros((b, 1), jnp.int32)],
                             axis=1)
    commit = jnp.where(j < a[:, None], padded, corr[:, None])
    return commit, a + 1


# --------------------------------------------------------------------------
# Engine scaffolding
# --------------------------------------------------------------------------
class SpeculativeMixin:
    """Propose→verify→commit scaffolding of the paged engine, which
    calls ``_init_spec(speculate_k)`` from __init__, implements
    ``_spec_verify_call(ready, proposals, n_prop)`` (the jitted verify
    dispatch; returns (commit, n_commit) device arrays and updates the
    cache/token vector) with the page-room hooks ``_spec_room(slot)``
    (proposal cap, -1 = cannot take one more token),
    ``_spec_starved(slots)`` and ``_spec_can_fuse(slot, rounds)``, and
    routes ``step()`` through ``_spec_step()`` when ``speculate_k > 0``.

    The speculative loop is SYNCHRONOUS (one sanctioned host_sync per
    round): the proposer needs the committed tokens on the host before
    it can propose the next continuation, so the verify readback cannot
    lag like the fused-decode pipeline. Each round still amortizes the
    weight stream over up to k+1 tokens per slot.

    With ``decode_steps_per_call > 1`` set alongside ``speculate_k``,
    ``step()`` routes through ``_spec_step_fused()`` and
    ``_spec_fused_call(ready, rounds)`` instead: the proposer moves ON
    DEVICE (``ngram_propose_device``) and ``rounds`` whole
    propose→verify→commit rounds fuse into one dispatch, so the
    host_sync amortizes ``rounds`` x on top of speculation's k+1 x."""

    # Longest n-gram the proposer tries to match (host-side knob; not
    # part of any jit key).
    spec_max_ngram = 3

    # History window the DEVICE proposer sees in fused rounds
    # (``_spec_step_fused``); host uploads the trailing ``H`` tokens
    # per slot each dispatch. Shapes a jitted program, so it is a
    # class-level constant, not a jit key.
    spec_hist_window = 64

    def _init_spec(self, speculate_k: Optional[int]) -> None:
        self.speculate_k = int(speculate_k or 0)
        if self.speculate_k < 0:
            raise ValueError(
                f'speculate_k must be >= 0, got {self.speculate_k}')
        self._spec_verify_fns: Dict[Tuple, Any] = {}
        self._spec_prepared: Optional[Dict[str, Dict[int, Any]]] = None
        self._spec_rounds = 0
        self._spec_slot_steps = 0     # (round, active slot) pairs
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_committed = 0

    # ------------------------------------------------------------ metrics
    def spec_metrics(self) -> Dict[str, Any]:
        """Stable-schema speculation gauges (all keys always present,
        zeros when speculation is off — scrapers see one schema)."""
        proposed = self._spec_proposed
        slot_steps = self._spec_slot_steps
        return {
            'speculate_k': self.speculate_k,
            'spec_rounds': self._spec_rounds,
            'spec_proposed': proposed,
            'spec_accepted': self._spec_accepted,
            'spec_committed': self._spec_committed,
            'spec_accept_rate': (self._spec_accepted / proposed
                                 if proposed else 0.0),
            # Mean tokens committed per slot per verify call (1..k+1):
            # the multiplier over one-token-per-pass decode.
            'spec_tokens_per_step': (self._spec_committed / slot_steps
                                     if slot_steps else 0.0),
        }

    # ----------------------------------------------------------- proposer
    def prepare_proposals(self) -> None:
        """Host-only n-gram matching for the current decodable slots.
        The serve loop calls this BEFORE taking the engine lock
        (graftcheck GC108: proposer host work never runs under the
        lock); results are keyed by (request_id, len(output)) and
        revalidated in ``_spec_build_proposals`` — a stale entry (the
        slot turned over or grew between prepare and use) is simply
        recomputed inline. Only the engine-loop thread mutates slot
        outputs, so the reads here are single-writer safe."""
        if not self.speculate_k:
            return
        prep: Dict[str, Dict[int, Any]] = {'key': {}, 'prop': {}}
        off = set(self._prefill_off)
        for slot, req in enumerate(list(self._slots)):
            if req is None or slot in off or req.hold \
                    or req.finish_time is not None:
                continue
            prep['key'][slot] = (req.request_id, len(req.output))
            prep['prop'][slot] = ngram_propose(
                req.prompt + req.output, self.speculate_k,
                max_ngram=self.spec_max_ngram)
        self._spec_prepared = prep

    def _spec_build_proposals(self, ready) -> Tuple[np.ndarray,
                                                    np.ndarray, List[int]]:
        """Fixed-shape [b, k] proposal matrix + per-slot valid counts.
        Each slot's count is capped by its remaining generation budget
        and sequence capacity (n_commit <= n_prop + 1 never overshoots
        either), and by the engine's ``_spec_room``. Returns
        (proposals, n_prop, starved_slots)."""
        k = self.speculate_k
        b = self.max_batch
        proposals = np.zeros((b, k), np.int32)
        n_prop = np.zeros(b, np.int32)
        starved: List[int] = []
        cached = self._spec_prepared
        self._spec_prepared = None
        for slot, req in enumerate(ready):
            if req is None:
                continue
            room = self._spec_room(slot)
            if room < 0:
                starved.append(slot)
                continue
            out = len(req.output)
            budget = min(req.max_new_tokens - out,
                         self.max_seq - len(req.prompt) - out) - 1
            room = min(room, max(0, budget))
            if room <= 0:
                continue
            if (cached is not None and cached['key'].get(slot)
                    == (req.request_id, out)):
                prop = cached['prop'][slot]
            else:
                prop = ngram_propose(req.prompt + req.output, k,
                                     max_ngram=self.spec_max_ngram)
            m = min(len(prop), room)
            proposals[slot, :m] = prop[:m]
            n_prop[slot] = m
        return proposals, n_prop, starved

    # ----------------------------------------------------------- the step
    def _spec_step(self) -> List[Tuple[int, int, bool]]:
        """One propose→verify→commit round over every decodable slot.
        Drains the async pipeline first (the proposer and the commit
        bookkeeping need host-complete outputs), then runs ONE verify
        program and commits its masked results. Emits 1..k+1 tokens per
        active slot."""
        from skypilot_tpu.telemetry import clock
        from skypilot_tpu.utils.host import host_sync
        events: List[Tuple[int, int, bool]] = []
        with self._prof.phase('readback'):
            while self._pending:
                events.extend(self._process_one())
        ready = self._decode_ready()
        if not any(r is not None for r in ready):
            return events
        round_t0 = clock.monotonic()
        with self._prof.phase('spec_verify'):
            proposals, n_prop, starved = \
                self._spec_build_proposals(ready)
            if starved:
                self._spec_starved(starved)
                ready = self._decode_ready()
                if not any(r is not None for r in ready):
                    return events
            commit, n_commit = self._spec_verify_call(ready, proposals,
                                                      n_prop)
            # THE sanctioned readback of the speculative loop (the
            # round is synchronous by design — see class docstring).
            commit_h = host_sync(commit)
            n_commit_h = host_sync(n_commit)
        round_t1 = clock.monotonic()
        self._spec_rounds += 1
        self._spec_proposed += int(n_prop.sum())
        for slot, req in enumerate(ready):
            if req is None or req.finish_time is not None:
                continue
            m = int(n_commit_h[slot])
            if m <= 0:
                continue
            self._spec_slot_steps += 1
            self._spec_accepted += m - 1
            self._spec_committed += m
            if req.trace is not None:
                req.trace.add('spec_round', round_t0, round_t1,
                              proposed=int(n_prop[slot]), committed=m)
            for j in range(m):
                token = int(commit_h[slot, j])
                req.output.append(token)
                self._slot_len[slot] += 1
                finished = self._finish_req(slot, req, token)
                events.append((req.request_id, token, finished))
                if finished:
                    break
        return events

    # ------------------------------------------------- fused (in-scan)
    def _spec_hist_state(self, ready) -> Tuple[np.ndarray, np.ndarray]:
        """Device-proposer inputs for the fused rounds: right-aligned
        history window ``[b, H]`` (left-padded with -1) and per-slot
        remaining-token budgets ``[b]``. ``rem`` is the most tokens the
        slot may still emit (generation budget and sequence capacity),
        so the in-scan cap ``n_prop <= rem - 1`` reproduces
        ``_spec_build_proposals``'s budget math round by round and
        commits never overshoot."""
        H = self.spec_hist_window
        b = self.max_batch
        hist = np.full((b, H), -1, np.int32)
        rem = np.zeros((b,), np.int32)
        for slot, req in enumerate(ready):
            if req is None:
                continue
            toks = (req.prompt + req.output)[-H:]
            hist[slot, H - len(toks):] = toks
            out = len(req.output)
            rem[slot] = max(0, min(req.max_new_tokens - out,
                                   self.max_seq - len(req.prompt) - out))
        return hist, rem

    def _spec_step_fused(self) -> List[Tuple[int, int, bool]]:
        """In-scan speculative verify: ``decode_steps_per_call`` rounds
        of propose→verify→commit fused into ONE jitted dispatch (a
        ``lax.scan`` over rounds with the DEVICE n-gram proposer and a
        gather-carried history window), then one sanctioned host_sync
        for the stacked commits. Composes the two amortization knobs:
        speculation's up-to-``k+1`` tokens per weight stream AND the
        multi-step pin's one dispatch per ``rounds`` verify rounds.

        Tokens a slot commits after finishing mid-scan (EOS hit in an
        earlier round — the device cannot see host finish state) are
        discarded at readback, same as vanilla multi-step decode past
        EOS; the ``rem`` carry guarantees the device never writes past
        ``max_new_tokens`` or the sequence capacity. Falls back to the
        synchronous single-round ``_spec_step`` when any active slot
        cannot reserve the fused KV growth up front (``_spec_can_fuse``
        — paged pool pressure)."""
        from skypilot_tpu.telemetry import clock
        from skypilot_tpu.utils.host import host_sync
        rounds = self.decode_steps_per_call or 1
        if rounds <= 1:
            return self._spec_step()
        events: List[Tuple[int, int, bool]] = []
        with self._prof.phase('readback'):
            while self._pending:
                events.extend(self._process_one())
        ready = self._decode_ready()
        if not any(r is not None for r in ready):
            return events
        if not all(self._spec_can_fuse(slot, rounds)
                   for slot, r in enumerate(ready) if r is not None):
            events.extend(self._spec_step())
            return events
        round_t0 = clock.monotonic()
        with self._prof.phase('spec_verify'):
            commits, n_commits, n_props = \
                self._spec_fused_call(ready, rounds)
            # THE sanctioned readback: one host_sync per ``rounds``
            # verify rounds (vs one per round in _spec_step).
            commits_h = host_sync(commits)
            n_commits_h = host_sync(n_commits)
            n_props_h = host_sync(n_props)
        round_t1 = clock.monotonic()
        self._spec_rounds += rounds
        for slot, req in enumerate(ready):
            if req is None or req.finish_time is not None:
                continue
            finished = False
            for r in range(rounds):
                m = int(n_commits_h[r, slot])
                if m <= 0:
                    continue
                self._spec_slot_steps += 1
                self._spec_proposed += int(n_props_h[r, slot])
                self._spec_accepted += m - 1
                self._spec_committed += m
                if req.trace is not None:
                    req.trace.add('spec_round', round_t0, round_t1,
                                  proposed=int(n_props_h[r, slot]),
                                  committed=m)
                for j in range(m):
                    token = int(commits_h[r, slot, j])
                    req.output.append(token)
                    self._slot_len[slot] += 1
                    finished = self._finish_req(slot, req, token)
                    events.append((req.request_id, token, finished))
                    if finished:
                        break
                if finished:
                    break
        return events
