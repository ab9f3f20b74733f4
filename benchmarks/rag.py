"""The RAG entry point: what ``rag_jobs`` traffic drives.

Set-up runs the ingest CLI's own ``main`` on the fixed corpus (the
standard-library packages of ``textgen``), through the seeded encoder at
e5-small-v2 widths into the native store and the device index, and fills the
chunk table up to the size a deployment holds with vectors made from the
seed.  Then ``POST /rag/jobs`` and the SSE stream are served as
``api/__main__.serve`` wires them (RagApi, in-memory bus and queue,
GraphAgent over MeteredLLM, RagWorker), with one difference: the LLM is the
benchmark's engine (weights from the seed), not a checkpoint ``load_qwen2``
reads, because a 7B checkpoint cannot be written and loaded in every run.

Ingest runs in this process, not as a child: every run is a new process,
and a child would start the encoder (``transformers`` import, tokenizer,
checkpoint) a second time, some 30 s of set-up that serve no request.
"""

from __future__ import annotations

import asyncio
import contextlib
import io
import json
import os
import shutil
import time
from pathlib import Path

import numpy as np

from benchmarks import textgen

NAMESPACE, REPO = "bench", "stdlib"


def _write_bert(out: Path, bert: dict, seed: int, files: list) -> None:
    """A seeded HF-layout BERT checkpoint with the tokenizer files
    ``JaxBertTextEncoder.from_pretrained`` reads.  The WordPiece vocabulary
    depends on the corpus alone and is kept between runs; the weights are
    written anew from every seed."""
    from safetensors.numpy import save_file

    out.mkdir(parents=True, exist_ok=True)
    stamp, want = out / "VOCAB.json", {"vocab_size": bert["vocab_size"],
                                       "corpus": list(textgen.CORPUS_PACKAGES)}
    if not (stamp.is_file() and json.loads(stamp.read_text()) == want):
        from tokenizers.implementations import BertWordPieceTokenizer

        tok = BertWordPieceTokenizer(lowercase=True)
        tok.train_from_iterator((ln for p in files for ln in p.read_text(errors="replace")
                                 .splitlines()), vocab_size=bert["vocab_size"],
                                show_progress=False)
        tok.save_model(str(out))
        (out / "tokenizer_config.json").write_text(json.dumps(
            {"tokenizer_class": "BertTokenizer", "do_lower_case": True, "model_max_length": 512}))
        stamp.write_text(json.dumps(want))
    cfg = {"model_type": "bert", "architectures": ["BertModel"], "max_position_embeddings": 512,
           "type_vocab_size": 2, "layer_norm_eps": 1e-12, **bert}
    (out / "config.json").write_text(json.dumps(cfg))
    rng = np.random.default_rng(int(seed) + 1)
    d, inter = bert["hidden_size"], bert["intermediate_size"]
    w = lambda *shape: rng.standard_normal(shape, dtype=np.float32) * 0.02  # noqa: E731
    one = lambda n: np.ones((n,), np.float32)  # noqa: E731
    zero = lambda n: np.zeros((n,), np.float32)  # noqa: E731
    t = {"embeddings.word_embeddings.weight": w(bert["vocab_size"], d),
         "embeddings.position_embeddings.weight": w(512, d),
         "embeddings.token_type_embeddings.weight": w(2, d),
         "embeddings.LayerNorm.weight": one(d), "embeddings.LayerNorm.bias": zero(d)}
    for i in range(bert["num_hidden_layers"]):
        p = f"encoder.layer.{i}."
        for name in ("attention.self.query", "attention.self.key", "attention.self.value",
                     "attention.output.dense"):
            t[p + name + ".weight"], t[p + name + ".bias"] = w(d, d), zero(d)
        t[p + "intermediate.dense.weight"], t[p + "intermediate.dense.bias"] = \
            w(inter, d), zero(inter)
        t[p + "output.dense.weight"], t[p + "output.dense.bias"] = w(d, inter), zero(d)
        for ln in ("attention.output.LayerNorm", "output.LayerNorm"):
            t[p + ln + ".weight"], t[p + ln + ".bias"] = one(d), zero(d)
    save_file(t, str(out / "model.safetensors"))


def _corpus_dir(out: Path, packages) -> Path:
    """The corpus as a directory the ingest CLI can read: a copy of the
    standard-library packages, made once."""
    stamp = out / "COPIED.json"
    if stamp.is_file() and json.loads(stamp.read_text()) == list(packages):
        return out
    shutil.rmtree(out, ignore_errors=True)
    std = textgen.corpus_files(packages)[0]
    root = next(p for p in std.parents if p.name.startswith("python3"))
    for f in textgen.corpus_files(packages):
        dst = out / f.relative_to(root)
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copyfile(f, dst)
    stamp.write_text(json.dumps(list(packages)))
    return out


def prepare(ses, log) -> None:
    """Environment of the single-pod deployment, encoder from the seed,
    ingest, and the index filled to deployment size.  Runs after the device
    check and before the engine is built."""
    rag_cfg = ses.config["rag"] if not ses.rehearse else ses.config["rehearse"]["rag"]
    work = ses.work
    store = work / "store"
    shutil.rmtree(store, ignore_errors=True)
    bert_dir = work / "e5-small-v2-seeded"
    packages = rag_cfg.get("corpus_packages", list(textgen.CORPUS_PACKAGES))
    _write_bert(bert_dir, rag_cfg["encoder"], ses.seed, textgen.corpus_files(packages))
    os.environ.update({
        "STORE_BACKEND": "native", "STORE_PATH": str(store), "EMBED_MODEL": str(bert_dir),
        "EMBED_DIM": str(rag_cfg["encoder"]["hidden_size"]),
        "DEVICE_INDEX": "on" if ses.rehearse else "auto",
        "LLM_BACKEND": "fake",  # the ingest summariser's; the API's LLM is wired below
        "QWEN_MAX_OUTPUT": str(rag_cfg["max_output_tokens"]),
        "CONTEXT_WINDOW": str(ses.needs.get("max_seq_len", ses.config["engine"]["max_seq_len"])),
        "WORKER_MAX_JOBS": str(max(16, int(ses.traffic.get("clients", 16)))),
        "TRACE_MAX_TRACES": "4096", "TRACE_MAX_SPANS": "512",
        "DEFAULT_NAMESPACE": NAMESPACE,
    })
    from githubrepostorag_tpu.ingest.__main__ import main as ingest_main

    t0 = time.monotonic()
    corpus = _corpus_dir(work / "corpus", packages)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):  # the CLI prints its record; stdout is the result's
        rc = ingest_main(["--local", str(corpus), "--namespace", NAMESPACE, "--repo", REPO])
    if rc != 0:
        raise SystemExit(f"ingest exited {rc}")
    text = buf.getvalue()
    record = json.loads(text[text.index("{"):text.rindex("}") + 1])
    log(f"ingest wrote {record.get('written')} in {time.monotonic() - t0:.1f}s")
    ses.ingest_record = record
    fill_index(ses, int(rag_cfg["index_chunks"]), log)


def fill_index(ses, total: int, log) -> None:
    """Vectors beyond the real corpus, from the seed: unit vectors in the
    chunk table, tagged with their own repo so that no filter of the agent
    selects them; they make the index the size a deployment holds."""
    from githubrepostorag_tpu.config import get_settings
    from githubrepostorag_tpu.store import get_store
    from githubrepostorag_tpu.store.base import Doc

    s = get_settings()
    store, table = get_store(), s.embeddings_table_chunk
    have = store.count(table)
    need = max(0, total - have)
    t0 = time.monotonic()
    rng = np.random.default_rng(int(ses.seed) + 2)
    dim = s.embed_dim
    step = 20000
    ses.synthetic_ids, kept = [], []
    for start in range(0, need, step):
        n = min(step, need - start)
        vecs = rng.standard_normal((n, dim), dtype=np.float32)
        vecs /= np.linalg.norm(vecs, axis=1, keepdims=True)
        store.upsert(table, [
            Doc(doc_id=f"synthetic:{start + i}", text=f"synthetic chunk {start + i}",
                metadata={"namespace": NAMESPACE, "repo": "synthetic", "scope": "chunk"},
                vector=vecs[i]) for i in range(n)])
        ses.synthetic_ids += [f"synthetic:{start + i}" for i in range(n)]
        kept.append(vecs)
    ses.synthetic_vecs = np.concatenate(kept) if kept else np.zeros((0, dim), np.float32)
    ses.index_rows = store.count(table)
    log(f"index: {have} real chunks + {need} seeded vectors in {time.monotonic() - t0:.1f}s")


def render_plan(plan: dict, traffic: dict, ses) -> None:
    for c in plan["clients"]:
        for r in c["requests"]:
            r["namespace"], r["top_k"] = NAMESPACE, int(traffic.get("top_k", 5))


class RagEntry:
    """``/rag/jobs`` as the API pod serves it in single-pod mode."""

    def __init__(self, ses) -> None:
        from githubrepostorag_tpu.agent import GraphAgent
        from githubrepostorag_tpu.api.app import RagApi
        from githubrepostorag_tpu.events import MemoryBus, MemoryCancelFlags, MemoryJobQueue
        from githubrepostorag_tpu.llm import InProcessLLM, set_llm
        from githubrepostorag_tpu.metrics import MeteredLLM
        from githubrepostorag_tpu.serving.async_engine import AsyncEngine
        from githubrepostorag_tpu.worker import RagWorker

        self.ses = ses
        bus, flags, queue = MemoryBus(), MemoryCancelFlags(), MemoryJobQueue()
        self.flags = flags
        self.api = RagApi(bus, flags, queue)
        self.async_engine = AsyncEngine(ses.engine)
        self.llm = InProcessLLM(self.async_engine, ses.tokenizer)
        set_llm(self.llm)
        self.worker = RagWorker(GraphAgent(MeteredLLM(self.llm)), bus, flags, queue)
        self._task = None

    async def start(self) -> str:
        port = await self.api.start(host="127.0.0.1", port=0)
        self._task = asyncio.ensure_future(self.worker.run_forever())
        return f"http://127.0.0.1:{port}"

    async def after_window(self, data: dict) -> dict:
        """Per-job call counts and LLM seconds from the flight recorder (the
        final event carries only the phases), the share of retrievals the
        device index answered, and the top-k comparison."""
        from githubrepostorag_tpu.obs.recorder import get_recorder

        # the client has left; jobs of the tail it no longer reads are
        # cancelled as a user would cancel them, and the engine runs dry
        # before anything else touches it
        for r in data["records"]:
            if r.get("job_id") and not r.get("done_t"):
                await self.flags.cancel(r["job_id"])
        engine = self.ses.engine
        deadline = time.monotonic() + 60
        while (engine.has_work() or engine.num_running) and time.monotonic() < deadline:
            await asyncio.sleep(0.1)
        rec = get_recorder()
        by_trace = {tid: spans for tid, spans, _ in rec.export_spans()}
        for r in data["records"]:
            tid = (r.get("final") or {}).get("trace_id") or r.get("trace_id")
            spans = by_trace.get(tid)
            if not spans or not r.get("final"):
                continue
            llm = [s for s in spans if s.name in ("llm.complete", "llm.stream",
                                                  "llm.complete_batch")]
            r["final"]["llm_calls"] = len(llm)
            r["final"]["llm_seconds"] = sum((s.end or s.start) - s.start for s in llm)
        return {"checks": self._checks(data)}

    def _checks(self, data: dict) -> dict:
        window = [r for r in data["records"] if r["phase"] == "window"]
        finals = sum(1 for r in window if r.get("finish_reason") == "final"
                     and (r.get("final") or {}).get("sources") is not None)
        out = {"every_job_final": (finals == len(window),
                                   f"jobs_ended_in_final {finals} of {len(window)} limit all")}
        out["index_topk"] = index_topk_check(self.ses)
        return out

    async def stop(self) -> None:
        self.worker.stop()
        if self._task is not None:
            self._task.cancel()
            await asyncio.gather(self._task, return_exceptions=True)
        await self.api.stop()
        self.llm.close()


def index_topk_check(ses, queries: int = 8, k: int = 10) -> tuple:
    """The device index's top-k against a plain cosine top-k over the same
    vectors (the seeded ones kept from set-up, the corpus's read back through
    the store's metadata lookup), for seeded queries."""
    from githubrepostorag_tpu.config import get_settings
    from githubrepostorag_tpu.store import get_store

    s = get_settings()
    store, table = get_store(), s.embeddings_table_chunk
    real = [d for d in store.find_by_metadata(table, {"repo": REPO}, limit=1_000_000)
            if d.vector is not None]
    ids = [d.doc_id for d in real] + ses.synthetic_ids
    if len(ids) != store.count(table):
        return (False, f"index_topk rows {len(ids)} read back of {store.count(table)} limit all")
    mat = np.concatenate([np.stack([np.asarray(d.vector, np.float32) for d in real]),
                          ses.synthetic_vecs]) if real else ses.synthetic_vecs
    mat = mat / np.maximum(np.linalg.norm(mat, axis=1, keepdims=True), 1e-12)
    rng = np.random.default_rng(int(ses.seed) + 3)
    ids_a = np.asarray(ids)
    worst = 1.0
    for _ in range(queries):
        q = mat[rng.integers(len(ids))] + 0.3 * rng.standard_normal(mat.shape[1]).astype(
            np.float32)
        q /= np.linalg.norm(q)
        want = set(ids_a[np.argsort(-(mat @ q), kind="stable")[:k]].tolist())
        got = {h.doc.doc_id for h in store.search(table, q, k=k)}
        worst = min(worst, len(want & got) / k)
    return (worst >= 0.9, f"index_topk_recall_min {worst:.3f} limit 0.9")
