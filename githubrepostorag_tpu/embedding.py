"""Text embedding service: the one encoder shared by ingest writes and
query-time retrieval (the reference instantiates four separate
HuggingFaceEmbeddings copies — graph_rag_retrievers.py:53,
vector_write_service.py:117, ingest_controller.py:376,
cassandra_service.py:127; here there is one service with two call shapes).

Two encoder backends behind one protocol:
  - ``JaxBertTextEncoder`` — the real path: HF tokenizer + the in-tree JAX
    BERT encoder (models/encoder.py), length-bucketed batches on TPU.
    e5-style ``query:``/``passage:`` prefixes applied when the model name
    says e5 (the reference's documented model is intfloat/e5-small-v2).
  - ``HashingTextEncoder`` — deterministic, dependency-free 384-d encoder
    (signed feature hashing of word/bigram tokens, L2-normalized).  The
    test backbone and the no-weights dev backend; cosine similarity tracks
    lexical overlap so retrieval behaves sensibly end-to-end.
"""

from __future__ import annotations

import functools
import hashlib
import re
from typing import Literal, Protocol, Sequence

import numpy as np

from githubrepostorag_tpu.config import get_settings
from githubrepostorag_tpu.utils import next_bucket
from githubrepostorag_tpu.utils.logging import get_logger
from githubrepostorag_tpu.obs import startup
from githubrepostorag_tpu.utils.profiling import annotate

logger = get_logger(__name__)

Kind = Literal["query", "passage"]


class TextEncoder(Protocol):
    dim: int

    def encode(self, texts: Sequence[str], kind: Kind = "passage") -> np.ndarray:
        """-> [N, dim] float32, L2-normalized rows."""
        ...


_WORD_RE = re.compile(r"[A-Za-z_][A-Za-z0-9_]+|[0-9]+")


@functools.lru_cache(maxsize=1 << 16)
def _hash_slot(tok: str, dim: int) -> tuple[int, float]:
    """md5(token) -> (feature index, sign).  Token vocabularies are heavily
    repeated across chunks of the same repo (and across test runs), so the
    md5 is memoized module-wide rather than recomputed per encode call."""
    digest = hashlib.md5(tok.encode("utf-8")).digest()
    return int.from_bytes(digest[:4], "little") % dim, 1.0 if digest[4] & 1 else -1.0


class HashingTextEncoder:
    """Signed feature hashing over words + bigrams, sublinear tf, L2 norm."""

    def __init__(self, dim: int | None = None) -> None:
        self.dim = dim or get_settings().embed_dim

    def _tokens(self, text: str) -> list[str]:
        words = [w.lower() for w in _WORD_RE.findall(text)]
        bigrams = [f"{a}_{b}" for a, b in zip(words, words[1:])]
        return words + bigrams

    def encode(self, texts: Sequence[str], kind: Kind = "passage") -> np.ndarray:
        out = np.zeros((len(texts), self.dim), dtype=np.float32)
        for i, text in enumerate(texts):
            counts: dict[str, int] = {}
            for tok in self._tokens(text):
                counts[tok] = counts.get(tok, 0) + 1
            for tok, count in counts.items():
                idx, sign = _hash_slot(tok, self.dim)
                out[i, idx] += sign * (1.0 + np.log(count))
            norm = np.linalg.norm(out[i])
            if norm > 0:
                out[i] /= norm
        return out


class JaxBertTextEncoder:
    """HF tokenizer + in-tree JAX BERT.  Batches are length-bucketed so XLA
    compiles a handful of shapes; big ingest batches saturate the MXU."""

    def __init__(
        self,
        params: dict,
        cfg,
        tokenizer,
        *,
        max_length: int = 512,
        batch_size: int = 64,
        e5_prefixes: bool = True,
        mesh=None,  # jax.sharding.Mesh with a dp axis -> data-parallel batches
    ) -> None:
        self.cfg = cfg
        self.tokenizer = tokenizer
        self.max_length = max_length
        self.batch_size = batch_size
        self.e5_prefixes = e5_prefixes
        self.dim = cfg.hidden_size
        self.mesh = mesh
        self._dp = mesh.shape.get("dp", 1) if mesh is not None else 1
        if mesh is not None:
            # ~33M params: replicate everywhere, shard the BATCH over dp
            # (parallel/sharding.py encoder_param_specs; SURVEY.md §2.3 row
            # "Data parallel — ingest embedding")
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.params = jax.device_put(params, NamedSharding(mesh, P()))
            self._batch_sharding = NamedSharding(mesh, P("dp", None))
        else:
            self.params = params
            self._batch_sharding = None

    @classmethod
    @startup.records("startup.encoder")
    def from_pretrained(cls, model_dir: str, **kw) -> "JaxBertTextEncoder":
        import json
        from pathlib import Path

        from transformers import AutoTokenizer

        from githubrepostorag_tpu.models.encoder import BertConfig, params_from_hf_state_dict

        root = Path(model_dir)
        hf_cfg = json.loads((root / "config.json").read_text())
        cfg = BertConfig(
            vocab_size=hf_cfg["vocab_size"],
            hidden_size=hf_cfg["hidden_size"],
            intermediate_size=hf_cfg["intermediate_size"],
            num_layers=hf_cfg["num_hidden_layers"],
            num_heads=hf_cfg["num_attention_heads"],
            max_position_embeddings=hf_cfg["max_position_embeddings"],
            type_vocab_size=hf_cfg.get("type_vocab_size", 2),
            layer_norm_eps=hf_cfg.get("layer_norm_eps", 1e-12),
        )
        state: dict = {}
        from safetensors import safe_open

        for shard in sorted(root.glob("*.safetensors")):
            with safe_open(str(shard), framework="np") as f:
                for key in f.keys():
                    state[key] = f.get_tensor(key)
        params = params_from_hf_state_dict(state, cfg)
        tokenizer = AutoTokenizer.from_pretrained(model_dir)
        kw.setdefault("e5_prefixes", "e5" in model_dir.lower())
        return cls(params, cfg, tokenizer, **kw)

    def _dp_rows(self, rows: int) -> int:
        """dp-sharded batches must divide evenly over the mesh."""
        if rows % self._dp:
            rows = -(-rows // self._dp) * self._dp
        return rows

    def length_buckets(self) -> list[int]:
        """Every token-length bucket ``encode`` can hand the jitted embed."""
        return sorted({next_bucket(n, self.max_length)
                       for n in range(1, self.max_length + 1)})

    def row_buckets(self) -> list[int]:
        """Every (dp-aligned) row bucket ``encode`` can hand the jitted
        embed — partial tail batches included."""
        return sorted({self._dp_rows(next_bucket(n, self.batch_size, minimum=8))
                       for n in range(1, self.batch_size + 1)})

    @startup.records("startup.encoder")
    def warmup(self) -> int:
        """Precompile ``embed`` over the full (rows x length) bucket ladder
        so no live ``encode`` ever pays an XLA compile — the same
        zero-live-recompile contract the serving engine's warmup keeps
        (and the tpulint SHP002 warmup-coverage rule checks statically).
        Returns the number of dispatches driven."""
        import jax.numpy as jnp

        from githubrepostorag_tpu.models.encoder import embed

        n = 0
        for rows in self.row_buckets():
            for bucket in self.length_buckets():
                ids = np.zeros((rows, bucket), dtype=np.int32)
                mask = np.zeros((rows, bucket), dtype=np.int32)
                mask[:, 0] = 1  # one real token per row, like a live batch
                ids_d, mask_d = jnp.asarray(ids), jnp.asarray(mask)
                if self._batch_sharding is not None:
                    import jax

                    ids_d = jax.device_put(ids_d, self._batch_sharding)
                    mask_d = jax.device_put(mask_d, self._batch_sharding)
                with annotate("encoder.warmup"):
                    embed(self.params, self.cfg, ids_d, mask_d).block_until_ready()
                n += 1
        logger.info("embedding: warmup precompiled %d bucket shapes", n)
        return n

    def encode(self, texts: Sequence[str], kind: Kind = "passage") -> np.ndarray:
        import jax.numpy as jnp

        from githubrepostorag_tpu.models.encoder import embed

        if self.e5_prefixes:
            prefix = "query: " if kind == "query" else "passage: "
            texts = [prefix + t for t in texts]

        out = np.zeros((len(texts), self.dim), dtype=np.float32)
        order = sorted(range(len(texts)), key=lambda i: len(texts[i]))
        for start in range(0, len(order), self.batch_size):
            idx = order[start : start + self.batch_size]
            enc = self.tokenizer(
                [texts[i] for i in idx],
                truncation=True,
                max_length=self.max_length,
                padding=False,
            )
            max_len = max(len(x) for x in enc["input_ids"])
            bucket = next_bucket(max_len, self.max_length)
            # bucket the row dim too: distinct partial-batch sizes must not
            # each compile a fresh XLA program
            rows = next_bucket(len(idx), self.batch_size, minimum=8)
            if rows % self._dp:  # dp-sharded batches must divide evenly
                rows = -(-rows // self._dp) * self._dp
            ids = np.zeros((rows, bucket), dtype=np.int32)
            mask = np.zeros((rows, bucket), dtype=np.int32)
            for row, toks in enumerate(enc["input_ids"]):
                ids[row, : len(toks)] = toks
                mask[row, : len(toks)] = 1
            ids_d, mask_d = jnp.asarray(ids), jnp.asarray(mask)
            if self._batch_sharding is not None:
                import jax

                ids_d = jax.device_put(ids_d, self._batch_sharding)
                mask_d = jax.device_put(mask_d, self._batch_sharding)
            with annotate("embed.batch", texts=len(idx)):  # dispatch to vectors on host
                vecs = np.asarray(embed(self.params, self.cfg, ids_d, mask_d))
            out[idx] = vecs[: len(idx)]
        return out


_encoder: TextEncoder | None = None


def get_encoder() -> TextEncoder:
    """Process-wide encoder: JAX BERT when EMBED_MODEL points at a local
    checkpoint dir, else the hashing fallback."""
    global _encoder
    if _encoder is None:
        import os

        model = get_settings().embed_model
        if model and os.path.isdir(model):
            import jax

            mesh = None
            if jax.device_count() > 1:
                from githubrepostorag_tpu.parallel import make_mesh, plan_for_devices

                mesh = make_mesh(plan_for_devices(jax.device_count(), role="ingest"))
            _encoder = JaxBertTextEncoder.from_pretrained(model, mesh=mesh)
            logger.info(
                "embedding: JAX BERT encoder from %s (dp=%d)",
                model, mesh.shape["dp"] if mesh else 1,
            )
        else:
            _encoder = HashingTextEncoder()
            logger.warning(
                "embedding: EMBED_MODEL=%r is not a local checkpoint directory — "
                "falling back to the lexical hashing encoder. Retrieval quality is "
                "degraded until a local BERT checkpoint is mounted and EMBED_MODEL "
                "points at it.",
                model,
            )
    return _encoder


def set_encoder(encoder: TextEncoder | None) -> None:
    global _encoder
    _encoder = encoder
