"""Speculative (draft-model assisted) decoding.

Reference: ``utils/speculative_decoding.py`` (``NeuronSpeculation``:15,
``_standard_assisted_decoding``:40, sampling acceptance in the Medusa
posterior path :189) — a smaller draft model proposes ``num_draft`` tokens
per round; the target model scores the whole chunk in ONE cached forward and
a prefix is accepted:

* **greedy** acceptance: longest prefix where the proposal equals the
  target's argmax (the reference's standard assisted mode);
* **sampling** acceptance (speculative sampling, Leviathan/Chen): proposal
  ``i`` accepted with prob ``min(1, p_target/p_draft)``; on first rejection
  the replacement token is drawn from ``normalize(max(p_t - p_d, 0))`` — the
  output distribution is exactly the target model's sampling distribution.

v2 runs the whole proposal loop as ONE jitted ``lax.scan`` program and the
acceptance math as one jitted call — three device round-trips per round
instead of one per draft token (VERDICT r1 weak #9).

v3 (:func:`speculative_decode_fused`) goes the rest of the way: ENTIRE
rounds — propose scan, chunked verify, accept/rollback, cache compaction,
residual resample — live inside one XLA program, with ``lax.scan`` over R
rounds per dispatch, so an R-round block costs ONE program call plus ONE
host read instead of ~5R round-trips (the PROFILE.md r5 3.8-6.7 ms
per-dispatch floor was the whole per-token intercept). The host loop
(:func:`speculative_generate`) remains the readable reference path; the
fused path is bit-exact against it by construction (shared ``_propose`` /
``_accept`` math, identical rng fold-in).

Cache rollback is the key mechanic: the chunked verify writes all proposed
positions into the KV cache; rejected tail positions are "rolled back" by
resetting the per-slot ``cache_index`` — later writes overwrite the stale
entries, and the length mask hides them meanwhile (the reference manipulates
its aliased KV buffers the same way).
"""

from __future__ import annotations

import time
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from neuronx_distributed_tpu.inference.causal_lm import (
    CausalLM,
    GenerationResult,
    _set_cache_index,
    infer_prompt_lengths,
    percentile_ms,
)


def _refuse_slot_rows(*lms: CausalLM) -> None:
    """A rejected draft rewinds ``cache_index`` and the rejected K/V sit
    behind the mask; a recurrent state has no such rewind."""
    for lm in lms:
        if getattr(lm, "slot_rows", ()):
            raise ValueError(
                f"speculative decoding rewinds the cache by position; a model with "
                f"per-slot state {lm.slot_rows} cannot take back a rejected draft")


def _propose(draft: CausalLM, num_draft: int, greedy: bool, temperature: float,
             params, cache, last_tok, rng):
    """γ-token draft proposal scan. ONE function traced by BOTH the host-loop
    proposer program and the fused R-round program — bit-exactness between
    the two paths rests on the math (including the rng fold-in order) being
    literally shared, not re-implemented."""

    def fwd(params, cache, tok):
        logits, mut = draft.model.apply(
            {"params": draft._resolve(params), "cache": cache}, tok,
            mutable=["cache"]
        )
        return logits[:, 0].astype(jnp.float32), mut["cache"]

    def step(carry, i):
        cache, tok, rng = carry
        logits, cache = fwd(params, cache, tok[:, None])
        if greedy:
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            # acceptance never reads draft probs in greedy mode — don't
            # materialize (γ, b, V) softmax outputs on the hot loop
            probs = jnp.zeros((logits.shape[0], 1), jnp.float32)
        else:
            rng, sub = jax.random.split(rng)
            nxt = jax.random.categorical(sub, logits / temperature).astype(jnp.int32)
            probs = jax.nn.softmax(logits / temperature, axis=-1)
        return (cache, nxt, rng), (nxt, probs)

    (cache, _, _), (toks, probs) = jax.lax.scan(
        step, (cache, last_tok, rng), jnp.arange(num_draft)
    )
    return toks, probs, cache  # (γ, b), (γ, b, V), cache


def _make_proposer(draft: CausalLM, num_draft: int, greedy: bool, temperature: float):
    """One jitted program drafting ``num_draft`` tokens (scan over decode
    steps) — kills the per-token host round-trip of v1."""

    def proposer(params, cache, last_tok, rng):
        toks, probs, cache = _propose(draft, num_draft, greedy, temperature,
                                      params, cache, last_tok, rng)
        # cache outputs pin the serving specs at every program boundary
        # (CausalLM._shard_out): the cache round-trips between separately
        # compiled programs lowered on the same specs — an unconstrained
        # output lets GSPMD hand back a layout the next call rejects
        return toks, probs, draft._shard_out(cache)

    return jax.jit(proposer, donate_argnums=(1,))


@partial(jax.jit, static_argnums=(4, 5))
def _accept(t_logits, proposals, draft_probs, rng, greedy: bool, temperature: float):
    """Vectorized acceptance for slot 0 (batch-1 speculation, like the
    reference's per-sequence loop). ``t_logits``: (γ+1, V) target logits at
    the chunk positions; ``proposals``: (γ,); ``draft_probs``: (γ, V).
    Returns (accepted_count, next_token)."""
    gamma = proposals.shape[0]
    t_logits = t_logits.astype(jnp.float32)
    if greedy:
        t_choice = jnp.argmax(t_logits, axis=-1).astype(jnp.int32)   # (γ+1,)
        matches = proposals == t_choice[:gamma]
        acc = jnp.sum(jnp.cumprod(matches.astype(jnp.int32)))
        return acc, t_choice[acc]
    p_t = jax.nn.softmax(t_logits / temperature, axis=-1)            # (γ+1, V)
    idx = jnp.arange(gamma)
    p_i = p_t[idx, proposals]
    q_i = draft_probs[idx, proposals]
    rng_u, rng_r = jax.random.split(rng)
    u = jax.random.uniform(rng_u, (gamma,))
    accept_i = u < jnp.minimum(1.0, p_i / jnp.maximum(q_i, 1e-20))
    acc = jnp.sum(jnp.cumprod(accept_i.astype(jnp.int32)))
    # replacement draw at the first rejection: residual (p_t - p_d)+ there;
    # all-accepted draws the bonus token from the target's own distribution
    q_ext = jnp.concatenate([draft_probs, jnp.zeros_like(p_t[-1:])], axis=0)
    resid = jnp.maximum(p_t[acc] - q_ext[acc], 0.0)
    norm = jnp.sum(resid)
    resid = jnp.where(norm > 0, resid / jnp.maximum(norm, 1e-20), p_t[acc])
    nxt = jax.random.categorical(rng_r, jnp.log(jnp.maximum(resid, 1e-30)))
    return acc, nxt.astype(jnp.int32)


def _build_round_block(target: CausalLM, draft: CausalLM, num_draft: int,
                       rounds: int, greedy: bool, temperature: float,
                       eos_token_id: Optional[int], pad_token_id: int,
                       max_new_tokens: int):
    """The fused R-round body: ``lax.scan`` over complete speculative rounds
    (draft γ-token propose scan -> target chunked verify -> accept/rollback ->
    cache-index compaction -> residual resample), so R rounds cost ONE
    program dispatch + ONE host read instead of the host loop's ~5R
    round-trips (PROFILE.md r5: 3.8-6.7 ms per-program dispatch floor).

    Exactness vs the host loop is the invariant: the proposal scan is the
    shared :func:`_propose`, acceptance is the shared :func:`_accept`, and the
    rng fold-in order (``split(rng, 3)`` per round) is identical — the fused
    path emits bit-identical tokens, greedy and sampled.

    Rounds after EOS/overrun are FROZEN via a length mask: ``n_keep`` drops
    to 0, emitted positions read ``pad_token_id``, ``cur_len``/``last_tok``
    carry through unchanged, and the cache-index reset makes the dead round's
    K/V writes invisible (they land at slots >= the frozen length; writes
    past ``max_seq_len`` are dropped by XLA scatter semantics)."""
    b = target.max_batch
    idx_vec = jnp.arange(num_draft + 1)

    def chunk_fwd(params, cache, ids):
        logits, mut = target.model.apply(
            {"params": target._resolve(params), "cache": cache}, ids,
            mutable=["cache"]
        )
        return logits, mut["cache"]

    def draft_step(params, cache, tok):
        _, mut = draft.model.apply(
            {"params": draft._resolve(params), "cache": cache}, tok,
            mutable=["cache"]
        )
        return mut["cache"]

    def block_fn(t_params, d_params, t_cache, d_cache,
                 last_tok, cur_len, emitted, done, rng):
        def round_body(carry, _):
            t_cache, d_cache, last_tok, cur_len, emitted, done, rng = carry
            rng, r_prop, r_acc = jax.random.split(rng, 3)
            last = jnp.full((b,), last_tok, jnp.int32)
            toks, probs, d_cache = _propose(
                draft, num_draft, greedy, temperature,
                d_params, d_cache, last, r_prop)
            chunk = jnp.concatenate(
                [jnp.full((b, 1), last_tok, jnp.int32),
                 toks[:, 0][None, :].repeat(b, 0)], axis=1)
            t_logits, t_cache = chunk_fwd(t_params, t_cache, chunk)
            acc, nxt = _accept(t_logits[0], toks[:, 0], probs[:, 0], r_acc,
                               greedy, temperature)
            proposals = toks[:, 0]                               # (γ,)
            # round emission vector: proposals[:acc] ++ [resample/bonus]
            props_ext = jnp.concatenate([proposals, proposals[-1:]])
            round_toks = jnp.where(idx_vec < acc, props_ext, nxt)
            n_keep = acc + 1
            if eos_token_id is not None:
                kept_eos = (round_toks == eos_token_id) & (idx_vec < n_keep)
                n_keep = jnp.where(jnp.any(kept_eos),
                                   jnp.argmax(kept_eos) + 1, n_keep)
            # length mask: dead rounds emit nothing; post-cutoff slots pad
            n_keep = jnp.where(done, 0, n_keep)
            round_toks = jnp.where(idx_vec < n_keep, round_toks, pad_token_id)
            new_last = jnp.where(done, last_tok,
                                 round_toks[jnp.maximum(n_keep - 1, 0)])
            # draft cache hole-fill: the proposer consumed [last, p1..p_{γ-1}];
            # slot old+γ must hold p_γ when all γ are accepted. Fed
            # UNCONDITIONALLY (branchless scan body): with a rejected tail the
            # write lands beyond the rolled-back index and is invisible —
            # exactly the host loop's accepted==γ refill, without the cond.
            d_cache = draft_step(d_params, d_cache,
                                 jnp.full((b, 1), proposals[-1], jnp.int32))
            cur_len = cur_len + n_keep
            emitted = emitted + n_keep
            done = done | (emitted >= max_new_tokens)
            if eos_token_id is not None:
                done = done | jnp.any(
                    (round_toks == eos_token_id) & (idx_vec < n_keep))
            # rollback/compaction: both caches' index vectors reset to the
            # accepted length (stale tails masked + overwritten later)
            lens = jnp.zeros((b,), jnp.int32).at[0].set(cur_len)
            t_cache = _set_cache_index(t_cache, lens)
            d_cache = _set_cache_index(d_cache, lens)
            return ((t_cache, d_cache, new_last, cur_len, emitted, done, rng),
                    (round_toks, n_keep, acc))

        carry = (t_cache, d_cache, last_tok, cur_len, emitted, done, rng)
        carry, (toks, keeps, accs) = jax.lax.scan(
            round_body, carry, None, length=rounds)
        t_cache, d_cache, last_tok, cur_len, emitted, done, rng = carry
        # program-boundary pin (CausalLM._shard_out): both caches feed
        # this same compiled block again next call — outputs must hand back
        # the serving-spec layout the block was lowered with
        return (target._shard_out(t_cache), draft._shard_out(d_cache),
                last_tok, cur_len, emitted, done, rng,
                toks, keeps, accs)

    return block_fn


def _compile_block(target: CausalLM, draft: CausalLM, t_cache, d_cache, rng,
                   num_draft: int, rounds: int, greedy: bool,
                   temperature: float, eos_token_id: Optional[int],
                   pad_token_id: int, max_new_tokens: int):
    """Lower + compile the R-round block against the live cache avals.
    Factored out so tests can wrap the returned executable and count its
    invocations (the ≤2-host-dispatches-per-block contract)."""
    block_fn = _build_round_block(target, draft, num_draft, rounds, greedy,
                                  temperature, eos_token_id, pad_token_id,
                                  max_new_tokens)
    z = jnp.int32(0)
    return jax.jit(block_fn, donate_argnums=(2, 3)).lower(
        target.params, draft.params, t_cache, d_cache,
        z, z, z, jnp.bool_(False), rng
    ).compile()


def speculative_decode_fused(
    target: CausalLM,
    draft: CausalLM,
    prompt_ids: np.ndarray,
    max_new_tokens: int,
    num_draft: int = 4,
    rounds_per_block: int = 8,
    eos_token_id: Optional[int] = None,
    pad_token_id: int = 0,
    prompt_length: Optional[int] = None,
    greedy: bool = True,
    temperature: float = 1.0,
    rng: Optional[jax.Array] = None,
) -> GenerationResult:
    """Single-program speculative decoding: entire rounds live on-device and
    ``rounds_per_block`` of them run per dispatch. Per R-round block the host
    performs exactly TWO operations — one compiled-program call and one result
    fetch — vs the host loop's ~5 round-trips per round. Output is
    token-identical to :func:`speculative_generate` (greedy and sampled; same
    rng fold-in discipline), which remains the readable reference path.

    ``result.stats`` reports ``fused_block_calls`` (compiled-program
    invocations), acceptance counters on the same surface as the host loop,
    and per-block wall percentiles."""
    _refuse_slot_rows(target, draft)
    if prompt_ids.shape[0] != 1:
        raise ValueError("speculative_decode_fused handles batch size 1")
    if rounds_per_block < 1:
        raise ValueError(f"rounds_per_block must be >= 1, got {rounds_per_block}")
    if target._decode is None:
        target.compile()
    if draft._decode is None:
        draft.compile()
    rng = rng if rng is not None else jax.random.key(0)

    b = target.max_batch
    s = prompt_ids.shape[1]
    length = (
        int(prompt_length)
        if prompt_length is not None
        else int(infer_prompt_lengths(prompt_ids, pad_token_id)[0])
    )
    if length + max_new_tokens + num_draft + 1 > target.config.max_seq_len:
        raise ValueError(
            f"prompt ({length}) + max_new_tokens ({max_new_tokens}) + draft window "
            f"({num_draft + 1}) exceeds max_seq_len {target.config.max_seq_len}"
        )
    bucket = target._bucket_for(s)
    ids = np.zeros((b, bucket), np.int32)
    ids[0, :s] = prompt_ids[0]

    t_logits, t_cache = target._prefill[bucket](target.params, jnp.asarray(ids))
    _, d_cache = draft._prefill[bucket](draft.params, jnp.asarray(ids))
    lens = np.zeros((b,), np.int32)
    lens[0] = length
    t_cache = _set_cache_index(t_cache, jnp.asarray(lens))
    d_cache = _set_cache_index(d_cache, jnp.asarray(lens))
    first = t_logits[0, length - 1].astype(jnp.float32)
    if greedy:
        first_tok = int(np.asarray(jnp.argmax(first)))
    else:
        rng, sub = jax.random.split(rng)
        first_tok = int(np.asarray(jax.random.categorical(sub, first / temperature)))

    out: list[int] = [first_tok]
    rounds = 0
    accepted_total = 0
    block_calls = 0
    block_times: list[float] = []
    done_h = len(out) >= max_new_tokens or (
        eos_token_id is not None and first_tok == eos_token_id)
    if not done_h:
        # compiled-block cache on the target instance: repeat generations
        # with the same (draft, γ, R, sampling, limits, bucket) reuse the
        # executable — without this every call would re-pay XLA compilation
        # and a "warmed" wall-clock measurement would be fiction. Keyed by
        # draft identity (both models outlive the cache in every sane use).
        key = (id(draft), num_draft, rounds_per_block, greedy,
               float(temperature), eos_token_id, pad_token_id,
               max_new_tokens, bucket)
        store = getattr(target, "_spec_fused_cache", None)
        if store is None:
            store = target._spec_fused_cache = {}
        compiled = store.get(key)
        if compiled is None:
            compiled = _compile_block(
                target, draft, t_cache, d_cache, rng, num_draft,
                rounds_per_block, greedy, temperature, eos_token_id,
                pad_token_id, max_new_tokens)
            store[key] = compiled
        last_tok = jnp.int32(first_tok)
        cur_len = jnp.int32(length)
        emitted = jnp.int32(1)
        done = jnp.bool_(False)
        while not done_h:
            t0 = time.perf_counter()
            # host op 1/2: the fused program call (R rounds, one dispatch)
            (t_cache, d_cache, last_tok, cur_len, emitted, done, rng,
             toks, keeps, accs) = compiled(
                target.params, draft.params, t_cache, d_cache,
                last_tok, cur_len, emitted, done, rng)
            block_calls += 1
            # host op 2/2: ONE result fetch for the whole block
            toks_np, keeps_np, accs_np, done_np = jax.device_get(
                (toks, keeps, accs, done))
            for r in range(rounds_per_block):
                k = int(keeps_np[r])
                if k == 0:
                    continue  # frozen (post-EOS/overrun) round
                out.extend(int(t) for t in toks_np[r, :k])
                rounds += 1
                accepted_total += int(accs_np[r])
            done_h = bool(done_np)
            block_times.append(time.perf_counter() - t0)

    out = out[:max_new_tokens]
    tokens = np.zeros((1, max_new_tokens), np.int64)
    tokens[0, : len(out)] = out
    pct = percentile_ms
    stats = {
        "rounds": rounds,
        "num_draft": num_draft,
        "proposed": rounds * num_draft,
        "accepted": accepted_total,
        "acceptance_rate": round(accepted_total / max(rounds * num_draft, 1), 4),
        "tokens_per_round": round(len(out) / max(rounds, 1), 2),
        "rounds_per_block": rounds_per_block,
        "fused_block_calls": block_calls,
        # the dispatch contract: one program call + one fetch per block
        "host_dispatches_per_block": 2,
        "block_ms_p50": pct(block_times, 50), "block_ms_p90": pct(block_times, 90),
    }
    return GenerationResult(tokens=tokens, lengths=np.asarray([len(out)], np.int32),
                            stats=stats)


def speculative_generate(
    target: CausalLM,
    draft: CausalLM,
    prompt_ids: np.ndarray,
    max_new_tokens: int,
    num_draft: int = 4,
    eos_token_id: Optional[int] = None,
    pad_token_id: int = 0,
    prompt_length: Optional[int] = None,
    greedy: bool = True,
    temperature: float = 1.0,
    rng: Optional[jax.Array] = None,
    collect_stats: bool = False,
) -> GenerationResult:
    """Assisted decoding, batch 1 (the reference's assisted loop is also
    per-sequence). ``greedy=False`` switches to sampling acceptance — the
    returned tokens are distributed exactly as target-model sampling at
    ``temperature``. Stops at ``eos_token_id``.

    ``collect_stats`` additionally times the draft and verify submodels,
    which costs TWO extra host syncs per round (the normal loop blocks only
    once, at the acceptance read) — leave it off outside benchmarking.
    Acceptance counts and per-round times ride on the existing sync and are
    always reported in ``result.stats``."""
    _refuse_slot_rows(target, draft)
    if prompt_ids.shape[0] != 1:
        raise ValueError("speculative_generate handles batch size 1")
    if target._decode is None:
        target.compile()
    if draft._decode is None:
        draft.compile()
    rng = rng if rng is not None else jax.random.key(0)

    # chunked verify program on the target: γ+1 tokens at the current index
    def chunk_fn(params, cache, ids):
        logits, mut = target.model.apply(
            {"params": target._resolve(params), "cache": cache}, ids,
            mutable=["cache"]
        )
        # program-boundary pin (CausalLM._shard_out): the cache feeds
        # this same AOT program again next round
        return logits, target._shard_out(mut["cache"])

    b = target.max_batch
    s = prompt_ids.shape[1]
    length = (
        int(prompt_length)
        if prompt_length is not None
        else int(infer_prompt_lengths(prompt_ids, pad_token_id)[0])
    )
    if length + max_new_tokens + num_draft + 1 > target.config.max_seq_len:
        raise ValueError(
            f"prompt ({length}) + max_new_tokens ({max_new_tokens}) + draft window "
            f"({num_draft + 1}) exceeds max_seq_len {target.config.max_seq_len}"
        )
    bucket = target._bucket_for(s)
    ids = np.zeros((b, bucket), np.int32)
    ids[0, :s] = prompt_ids[0]

    t_logits, t_cache = target._prefill[bucket](target.params, jnp.asarray(ids))
    d_logits, d_cache = draft._prefill[bucket](draft.params, jnp.asarray(ids))
    lens = np.zeros((b,), np.int32)
    lens[0] = length
    t_cache = _set_cache_index(t_cache, jnp.asarray(lens))
    d_cache = _set_cache_index(d_cache, jnp.asarray(lens))
    first = t_logits[0, length - 1].astype(jnp.float32)
    if greedy:
        last_tok = int(np.asarray(jnp.argmax(first)))
    else:
        rng, sub = jax.random.split(rng)
        last_tok = int(np.asarray(jax.random.categorical(sub, first / temperature)))

    proposer = _make_proposer(draft, num_draft, greedy, temperature)
    chunk_compiled = jax.jit(chunk_fn, donate_argnums=(1,)).lower(
        target.params, t_cache, jnp.zeros((b, num_draft + 1), jnp.int32)
    ).compile()

    out: list[int] = [last_tok]
    cur_len = length
    rounds = 0
    accepted_total = 0
    round_times: list[float] = []
    draft_times: list[float] = []
    verify_times: list[float] = []
    while len(out) < max_new_tokens and (
        eos_token_id is None or out[-1] != eos_token_id
    ):
        t_round = time.perf_counter()
        # 1. draft proposes γ tokens in ONE device program
        rng, r_prop, r_acc = jax.random.split(rng, 3)
        last = jnp.full((b,), out[-1], jnp.int32)
        toks, probs, d_cache = proposer(draft.params, d_cache, last, r_prop)
        if collect_stats:  # extra host sync — benchmarking only
            jax.block_until_ready(toks)
            draft_times.append(time.perf_counter() - t_round)
        # 2. target scores [last, p1..pγ] in one chunked forward
        t_verify = time.perf_counter()
        chunk = jnp.concatenate(
            [jnp.full((b, 1), out[-1], jnp.int32), toks[:, 0][None, :].repeat(b, 0)],
            axis=1,
        )
        t_logits, t_cache = chunk_compiled(target.params, t_cache, chunk)
        if collect_stats:  # extra host sync — benchmarking only
            jax.block_until_ready(t_logits)
            verify_times.append(time.perf_counter() - t_verify)
        # 3. acceptance math in one device call
        acc_dev, next_dev = _accept(
            t_logits[0], toks[:, 0], probs[:, 0], r_acc, greedy, temperature
        )
        accepted = int(np.asarray(acc_dev))
        proposals = [int(t) for t in np.asarray(toks[:, 0])]
        new_tokens = proposals[:accepted] + [int(np.asarray(next_dev))]
        if eos_token_id is not None and eos_token_id in new_tokens:
            # stop at EOS: drop everything past it (reference assisted
            # decoding stops on eos_token_id)
            new_tokens = new_tokens[: new_tokens.index(eos_token_id) + 1]
        out.extend(new_tokens)
        cur_len += len(new_tokens)
        # Draft cache bookkeeping. The proposer wrote K/V for its γ inputs
        # [out_prev, p1..p_{γ-1}] at positions old..old+γ-1. The accepted
        # sequence needs positions old..old+accepted holding
        # [out_prev, p1..p_accepted]:
        # * accepted < γ — everything needed is already written; rolling the
        #   index back below both invalidates the rejected tail and avoids
        #   any replay;
        # * accepted == γ — position old+γ must hold p_γ, which the draft
        #   never consumed: feed it once (logits discarded) to fill the hole.
        if accepted == num_draft:
            _, d_cache = draft._decode(draft.params, d_cache,
                                       jnp.full((b, 1), proposals[-1], jnp.int32))
        # roll both caches to the accepted length (stale tail entries are
        # masked now and overwritten by later writes)
        lens[0] = cur_len
        t_cache = _set_cache_index(t_cache, jnp.asarray(lens))
        d_cache = _set_cache_index(d_cache, jnp.asarray(lens))
        rounds += 1
        accepted_total += accepted
        round_times.append(time.perf_counter() - t_round)

    out = out[:max_new_tokens]
    tokens = np.zeros((1, max_new_tokens), np.int64)
    tokens[0, : len(out)] = out
    pct = percentile_ms
    stats = {
        "rounds": rounds,
        "num_draft": num_draft,
        "proposed": rounds * num_draft,
        "accepted": accepted_total,
        "acceptance_rate": round(accepted_total / max(rounds * num_draft, 1), 4),
        # each round also emits one token from the target's own distribution
        "tokens_per_round": round(len(out) / max(rounds, 1), 2),
        "round_ms_p50": pct(round_times, 50), "round_ms_p90": pct(round_times, 90),
        "draft_ms_p50": pct(draft_times, 50), "draft_ms_p90": pct(draft_times, 90),
        "verify_ms_p50": pct(verify_times, 50), "verify_ms_p90": pct(verify_times, 90),
    }
    return GenerationResult(tokens=tokens, lengths=np.asarray([len(out)], np.int32),
                            stats=stats)
