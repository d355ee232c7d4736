"""Deterministic synthetic data pipelines (the container is offline).

Three generators mirror the paper's data regimes (DESIGN.md §8):

  * `synthetic_images` — piecewise-smooth scenes with oriented edges and
    gradients: the statistics dictionary learning exploits in the van
    Hateren natural-image experiments (edge-like atoms emerge).
  * `topic_documents` — tf-idf-like topic-mixture documents over an
    M-dim vocabulary with held-out novel topics appearing at chosen
    time-steps: the TDT2 stand-in for novel-document detection.
  * `TokenStream` / `lm_batches` — a deterministic Zipf-ish Markov token
    stream for LM training (structured enough that loss decreases).

Everything is seeded and cheap to regenerate on every host — at 1000-node
scale the data pipeline is sharded by `host_index/host_count` slicing, which
`TokenStream` exposes directly.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Tuple

import numpy as np


# ---------------------------------------------------------------------------
# Images (denoising experiment)
# ---------------------------------------------------------------------------


def synthetic_images(n: int, size: int = 64, seed: int = 0) -> np.ndarray:
    """(n, size, size) piecewise-smooth images in [0, 1]."""
    rng = np.random.default_rng(seed)
    xs, ys = np.meshgrid(np.arange(size), np.arange(size), indexing="ij")
    out = np.zeros((n, size, size), np.float32)
    for i in range(n):
        img = np.zeros((size, size), np.float32)
        # smooth background gradient
        gx, gy = rng.normal(size=2) / size
        img += gx * xs + gy * ys + rng.uniform(0.2, 0.8)
        # a few random oriented half-plane edges with intensity steps
        for _ in range(rng.integers(2, 6)):
            theta = rng.uniform(0, np.pi)
            c = rng.uniform(0.2, 0.8) * size
            halfplane = (np.cos(theta) * xs + np.sin(theta) * ys) > c
            img += rng.uniform(-0.5, 0.5) * halfplane
        # a rectangle or two
        for _ in range(rng.integers(1, 3)):
            x0, y0 = rng.integers(0, size - 8, size=2)
            w, h = rng.integers(4, size // 2, size=2)
            img[x0 : x0 + w, y0 : y0 + h] += rng.uniform(-0.4, 0.4)
        img -= img.min()
        img /= max(img.max(), 1e-6)
        out[i] = img
    return out


def noisy_version(images: np.ndarray, sigma: float = 0.2, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return (images + sigma * rng.standard_normal(images.shape)).astype(np.float32)


def patch_dataset(
    images: np.ndarray, patch: int = 10, n_patches: int = 20000, seed: int = 2,
    remove_dc: bool = True,
) -> np.ndarray:
    """(n_patches, patch*patch) random patches, column-major stacked like the
    paper, optionally DC-removed."""
    rng = np.random.default_rng(seed)
    n, h, w = images.shape
    idx_img = rng.integers(0, n, n_patches)
    idx_i = rng.integers(0, h - patch + 1, n_patches)
    idx_j = rng.integers(0, w - patch + 1, n_patches)
    out = np.empty((n_patches, patch * patch), np.float32)
    for t in range(n_patches):
        p = images[idx_img[t], idx_i[t] : idx_i[t] + patch, idx_j[t] : idx_j[t] + patch]
        out[t] = p.T.reshape(-1)  # column-major
    if remove_dc:
        out -= out.mean(axis=1, keepdims=True)
    return out


# ---------------------------------------------------------------------------
# Planted sparse-code sample stream (streaming-service workload)
# ---------------------------------------------------------------------------


def planted_atoms(
    cols: np.ndarray, m: int, nonneg: bool = False, seed: int = 0
) -> np.ndarray:
    """(m, len(cols)) float32 unit-norm columns of the planted dictionary.

    Atom j is drawn from its own stream, seeded by (seed, j), so any subset
    of a K-atom dictionary is generated without materializing the rest: a
    stream of n samples touches at most n * sparsity atoms, whatever K is."""
    out = np.empty((m, len(cols)), np.float32)
    for c, j in enumerate(cols):
        out[:, c] = np.random.default_rng((seed, int(j))).standard_normal(
            m, dtype=np.float32
        )
    if nonneg:
        np.abs(out, out=out)
    out /= np.linalg.norm(out, axis=0, keepdims=True)
    return out


def sparse_stream(
    n: int,
    m: int = 32,
    k_true: int = 48,
    sparsity: int = 3,
    noise: float = 0.01,
    nonneg: bool = False,
    seed: int = 0,
    return_dictionary: bool = False,
):
    """(n, m) float32 stream of samples x = W0 y + noise, y `sparsity`-sparse.

    The canonical planted sparse-code model used by the quickstarts, the
    learner tests, and the streaming-service/serve-throughput workloads
    (deterministic, cheap, single-pass).  Only the atoms the samples use
    are generated (`planted_atoms`), so host memory and time scale with n,
    not with k_true.  With `return_dictionary=True` also returns the whole
    planted W0 (m, k_true) for recovery checks."""
    rng = np.random.default_rng(seed)
    # drawn sample by sample, so the first n' samples of a longer stream
    # are the n'-sample stream
    idx = np.empty((n, sparsity), np.int64)
    coef = np.empty((n, sparsity), np.float32)
    X = np.empty((n, m), np.float32)
    for i in range(n):
        idx[i] = rng.choice(k_true, sparsity, replace=False)
        sign = 1.0 if nonneg else rng.choice([-1.0, 1.0], sparsity)
        coef[i] = rng.uniform(0.5, 1.5, sparsity) * sign
        X[i] = noise * rng.standard_normal(m, dtype=np.float32)
    used, pos = np.unique(idx, return_inverse=True)
    atoms = planted_atoms(used, m, nonneg, seed)  # (m, n_used)
    X += np.einsum("nsm,ns->nm", atoms.T[pos.reshape(n, sparsity)], coef)
    if nonneg:
        np.abs(X, out=X)
    if return_dictionary:
        return X, planted_atoms(np.arange(k_true), m, nonneg, seed)
    return X


# ---------------------------------------------------------------------------
# Topic documents (novel-document detection experiment)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TopicStream:
    docs: np.ndarray  # (T, M) unit-norm nonneg tf-idf-like vectors
    labels: np.ndarray  # (T,) topic id per document
    novel_steps: dict  # step -> set of topic ids first seen at that step


def topic_documents(
    m_vocab: int = 500,
    n_topics: int = 30,
    docs_per_step: int = 500,
    n_steps: int = 8,
    topics_per_step: int = 3,
    words_per_topic: int = 40,
    seed: int = 0,
) -> TopicStream:
    """Documents arrive in blocks; each block may introduce novel topics.

    Topic k has a sparse word distribution; a document mixes 1-2 topics with
    Dirichlet weights + word noise, then is normalized to unit l2 norm
    (matching the paper's preprocessing).
    """
    rng = np.random.default_rng(seed)
    topics = np.zeros((n_topics, m_vocab), np.float32)
    for k in range(n_topics):
        words = rng.choice(m_vocab, words_per_topic, replace=False)
        topics[k, words] = rng.gamma(2.0, 1.0, words_per_topic)
        topics[k] /= topics[k].sum()

    # Topic schedule: steps introduce new topics progressively.
    introduced: list[int] = []
    novel_steps: dict[int, set] = {}
    docs, labels = [], []
    for s in range(n_steps + 1):  # step 0 = the initialization block
        new = list(range(len(introduced), min(len(introduced) + topics_per_step, n_topics)))
        if s == 0:
            new = list(range(0, max(topics_per_step * 2, 4)))
        novel_steps[s] = set(new) if s > 0 else set()
        introduced.extend(new)
        for _ in range(docs_per_step):
            # novel docs appear with prob ~ share of new topics
            if s > 0 and new and rng.random() < 0.3:
                k = int(rng.choice(new))
            else:
                old = introduced[: len(introduced) - len(new)] or introduced
                k = int(rng.choice(old))
            mix = topics[k].copy()
            if rng.random() < 0.3 and len(introduced) > 1:
                k2 = int(rng.choice(introduced))
                w = rng.uniform(0.2, 0.5)
                mix = (1 - w) * mix + w * topics[k2]
            counts = rng.poisson(mix * 200)
            v = counts.astype(np.float32) + 0.01 * rng.random(m_vocab).astype(np.float32)
            v /= max(np.linalg.norm(v), 1e-6)
            docs.append(v)
            labels.append(k)
    return TopicStream(
        docs=np.stack(docs).reshape(n_steps + 1, docs_per_step, m_vocab),
        labels=np.array(labels).reshape(n_steps + 1, docs_per_step),
        novel_steps=novel_steps,
    )


# ---------------------------------------------------------------------------
# LM token stream
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TokenStream:
    """Deterministic Markov-bigram token stream, shardable by host.

    The transition structure gives each token ~32 likely successors, so a
    model that learns it drops from ln(V) to ~ln(32) nats — enough signal
    for the end-to-end training example to show a real learning curve.
    """

    vocab: int
    seed: int = 0
    branching: int = 32

    def __post_init__(self):
        rng = np.random.default_rng(self.seed)
        self._succ = rng.integers(0, self.vocab, (self.vocab, self.branching))

    def batches(
        self,
        batch: int,
        seq: int,
        n_batches: int,
        host_index: int = 0,
        host_count: int = 1,
    ) -> Iterator[np.ndarray]:
        rng = np.random.default_rng(self.seed + 1 + host_index)
        for _ in range(n_batches):
            toks = np.empty((batch, seq), np.int64)
            state = rng.integers(0, self.vocab, batch)
            for t in range(seq):
                toks[:, t] = state
                choice = rng.integers(0, self.branching, batch)
                state = self._succ[state, choice]
            yield toks.astype(np.int32)


def lm_batches(vocab: int, batch: int, seq: int, n_batches: int, seed: int = 0):
    return TokenStream(vocab, seed).batches(batch, seq, n_batches)


def audio_batches(frame_dim: int, vocab: int, batch: int, seq: int, n_batches: int,
                  mask_frac: float = 0.08, seed: int = 0):
    """HuBERT-style masked-prediction batches: features + cluster targets."""
    rng = np.random.default_rng(seed)
    # cluster centroids tie features to targets so the task is learnable
    centroids = rng.standard_normal((vocab, frame_dim)).astype(np.float32)
    for _ in range(n_batches):
        targets = rng.integers(0, vocab, (batch, seq))
        feats = centroids[targets] + 0.3 * rng.standard_normal((batch, seq, frame_dim)).astype(np.float32)
        mask = rng.random((batch, seq)) < mask_frac
        feats = feats.copy()
        feats[mask] = 0.0  # masked frames are zeroed (stub for the learned mask emb)
        yield {
            "features": feats.astype(np.float32),
            "targets": targets.astype(np.int32),
            "mask": mask,
        }


def vlm_batches(vocab: int, n_img_tokens: int, vision_dim: int, batch: int,
                seq_text: int, n_batches: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    stream = TokenStream(vocab, seed)
    for toks in stream.batches(batch, seq_text, n_batches):
        yield {
            "tokens": toks,
            "img_embeds": rng.standard_normal((batch, n_img_tokens, vision_dim)).astype(np.float32),
        }
