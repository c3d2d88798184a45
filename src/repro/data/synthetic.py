"""Deterministic synthetic data pipeline.

The training objective must be *learnable* (not uniform noise) so optimizer
comparisons (SlowMo vs base) are meaningful: we sample token streams from a
fixed random first-order Markov chain with temperature-controlled entropy.
A model that learns the transition matrix reaches the chain's conditional
entropy; the gap to it is the optimizable signal.

The chain is LOW-RANK: the transition logits from token ``a`` to token ``b``
are ``E_in[a] . E_out[b]`` with (V, rank) factors drawn from the seed, so the
sampler holds O(V * rank) numbers instead of a dense (V, V) matrix — at a
50k vocabulary one dense f32 matrix alone is 10 GB.

Worker heterogeneity (the D_i in Eq. (1) of the paper): each worker draws
from a worker-specific interpolation between the shared chain and a
worker-local chain, controlled by ``heterogeneity`` in [0, 1].  This lets
experiments dial the inter-worker gradient discrepancy zeta^2 of Corollary 1.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

RANK = 64  # rank of the transition logits
ENTROPY_MAX_VOCAB = 4096  # chain_entropy builds the dense (V, V) matrix


@dataclasses.dataclass(frozen=True)
class MarkovLMConfig:
    vocab_size: int = 256
    temperature: float = 1.2  # lower => peakier transitions (more learnable)
    heterogeneity: float = 0.0  # 0: iid workers; 1: fully worker-local chains
    seed: int = 0


def _chain_factors(key, vocab: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(E_in, E_out) of one chain, scaled so each logit has unit variance."""
    rank = min(RANK, vocab)
    k_in, k_out = jax.random.split(key)
    e_in = jax.random.normal(k_in, (vocab, rank)) * rank**-0.5
    return e_in, jax.random.normal(k_out, (vocab, rank))


def make_markov_sampler(cfg: MarkovLMConfig, num_workers: int):
    """Returns sample(step, tau, per_worker_batch, seq) -> (tau, W, B, S) int32."""
    base_key = jax.random.PRNGKey(cfg.seed)
    e_in, e_out = _chain_factors(jax.random.fold_in(base_key, 1), cfg.vocab_size)
    h = cfg.heterogeneity
    if h:
        # mixed logits (1-h) * shared + h * local, as one rank-2r chain per
        # worker: (W, V, 2r) factors
        local = [
            _chain_factors(jax.random.fold_in(base_key, 100 + w), cfg.vocab_size)
            for w in range(num_workers)
        ]
        e_in = jnp.stack(
            [jnp.concatenate([(1 - h) * e_in, h * li], -1) for li, _ in local]
        )
        e_out = jnp.stack([jnp.concatenate([e_out, lo], -1) for _, lo in local])
    else:
        e_in, e_out = e_in[None], e_out[None]
    e_in = e_in / cfg.temperature
    # one factor set shared by every worker, or one per worker
    wsel = jnp.arange(num_workers) if h else jnp.zeros(num_workers, jnp.int32)

    # the factors are ARGUMENTS, not constants baked into the program
    @functools.partial(jax.jit, static_argnums=(3, 4, 5))
    def _sample(e_in, e_out, step, tau: int, batch: int, seq: int):
        key = jax.random.fold_in(jax.random.fold_in(base_key, 7), step)
        k0, kseq = jax.random.split(key)
        shape = (tau, num_workers, batch)
        first = jax.random.randint(k0, shape, 0, cfg.vocab_size)
        w = wsel[None, :, None]
        out_w = e_out[wsel]  # (W, V, r)

        def body(tok, k):
            emb = e_in[w, tok]  # (tau, W, B, r)
            logits = jnp.einsum("twbr,wvr->twbv", emb, out_w)
            nxt = jax.random.categorical(k, logits)
            return nxt, nxt

        _, toks = jax.lax.scan(body, first, jax.random.split(kseq, seq - 1))
        toks = jnp.concatenate([first[None], toks], axis=0)  # (S, tau, W, B)
        return jnp.transpose(toks, (1, 2, 3, 0)).astype(jnp.int32)

    def sample(step: int, tau: int, batch: int, seq: int):
        return _sample(e_in, e_out, step, tau, batch, seq)

    return sample


def chain_entropy(cfg: MarkovLMConfig) -> float:
    """Stationary conditional entropy of the *shared* chain (loss floor, nats).

    Builds the dense (V, V) transition matrix on the host, so it is limited
    to vocabularies up to ``ENTROPY_MAX_VOCAB``."""
    if cfg.vocab_size > ENTROPY_MAX_VOCAB:
        raise ValueError(
            f"chain_entropy builds a dense (V, V) matrix; vocab_size="
            f"{cfg.vocab_size} exceeds {ENTROPY_MAX_VOCAB}"
        )
    key = jax.random.PRNGKey(cfg.seed)
    e_in, e_out = _chain_factors(jax.random.fold_in(key, 1), cfg.vocab_size)
    logits = np.asarray(e_in, np.float64) @ np.asarray(e_out, np.float64).T
    logits = logits / cfg.temperature
    P = np.exp(logits - logits.max(-1, keepdims=True))
    P /= P.sum(-1, keepdims=True)
    # stationary distribution via power iteration
    pi = np.ones(cfg.vocab_size) / cfg.vocab_size
    for _ in range(200):
        pi = pi @ P
        pi /= pi.sum()
    H = -np.sum(pi[:, None] * P * np.log(P + 1e-12))
    return float(H)


def make_audio_sampler(vocab: int, frontend_dim: int, num_workers: int, seed: int = 0):
    """Synthetic HuBERT-style batches: features + cluster labels + mask.

    Labels are a (fixed random) linear quantization of the features, so the
    masked-prediction objective is learnable.
    """
    key = jax.random.PRNGKey(seed)
    codebook = jax.random.normal(jax.random.fold_in(key, 1), (frontend_dim, vocab))

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def sample(step: int, tau: int, batch: int, seq: int):
        k = jax.random.fold_in(jax.random.fold_in(key, 7), step)
        k1, k2 = jax.random.split(k)
        feats = jax.random.normal(k1, (tau, num_workers, batch, seq, frontend_dim))
        labels = jnp.argmax(jnp.einsum("twbsf,fv->twbsv", feats, codebook), axis=-1)
        mask = jax.random.bernoulli(k2, 0.3, (tau, num_workers, batch, seq))
        return {
            "features": feats,
            "labels": labels.astype(jnp.int32),
            "mask": mask,
        }

    return sample
