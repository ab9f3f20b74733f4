"""Text the traffic is made of: the corpus, the tokenizer that stands for
the model's, and prompts of an exact length in tokens.

The corpus is a fixed list of standard-library packages of the installed
Python: the same text in every checkout, whatever a later PR does to the
repo's own sources.  The tokenizer is a byte-level BPE trained on it, padded
with filler entries up to the model's vocabulary so that every id a model
with random weights emits decodes to text (Qwen2's own tokenizer covers its
vocabulary too), with the ChatML specials as the last three ids.
"""

from __future__ import annotations

import json
import random
import sysconfig
from pathlib import Path

CORPUS_PACKAGES = ("asyncio", "email", "http", "logging", "concurrent", "urllib", "json",
                   "collections")
CHATML = ["<|endoftext|>", "<|im_start|>", "<|im_end|>"]


def corpus_files(packages=CORPUS_PACKAGES) -> list[Path]:
    std = Path(sysconfig.get_paths()["stdlib"])
    files: list[Path] = []
    for pkg in packages:
        files += sorted(p for p in (std / pkg).rglob("*.py") if "test" not in p.parts)
    if not files:
        raise RuntimeError(f"no standard-library sources under {std}")
    return files


def build_tokenizer(out: Path, vocab_total: int, bpe_vocab: int = 32000) -> Path:
    """Write ``out/tokenizer.json`` (+ ``tokenizer_config.json``) unless a
    stamp says it is already the one asked for."""
    want = {"vocab_total": vocab_total, "bpe_vocab": bpe_vocab, "corpus": list(CORPUS_PACKAGES)}
    stamp = out / "BUILT.json"
    if stamp.is_file() and json.loads(stamp.read_text()) == want:
        return out
    from tokenizers.implementations import ByteLevelBPETokenizer

    out.mkdir(parents=True, exist_ok=True)
    texts = [p.read_text(errors="replace") for p in corpus_files()]
    tok = ByteLevelBPETokenizer()
    tok.train_from_iterator((ln for t in texts for ln in t.splitlines()),
                            vocab_size=bpe_vocab, show_progress=False)
    path = out / "tokenizer.json"
    tok.save(str(path))
    spec = json.loads(path.read_text())
    vocab = spec["model"]["vocab"]
    n = len(vocab)
    first_special = vocab_total - len(CHATML)
    if n > first_special:
        raise RuntimeError(f"trained vocabulary {n} exceeds the model's {vocab_total}")
    for i in range(n, first_special):  # filler: decodes to text, never encoded
        vocab[f"Ġq{i:06d}"] = i
    spec["added_tokens"] = [
        {"id": first_special + k, "content": s, "single_word": False, "lstrip": False,
         "rstrip": False, "normalized": False, "special": True}
        for k, s in enumerate(CHATML)]
    path.write_text(json.dumps(spec))
    (out / "tokenizer_config.json").write_text(json.dumps({"eos_token": "<|im_end|>"}))
    stamp.write_text(json.dumps(want))
    return out


class Prompts:
    """Prompts of an exact token count: words that are one token each when
    written with a leading space, around a fixed ChatML overhead."""

    def __init__(self, tokenizer, n_words: int = 1500) -> None:
        self.tok = tokenizer
        words = []
        for s, i in sorted(tokenizer.vocab.items(), key=lambda kv: kv[1]):
            w = s[1:]
            if s.startswith("Ġ") and 3 <= len(w) <= 10 and w.isascii() and w.isalpha() \
                    and w.islower():
                if tokenizer.encode(" " + w) == [i]:
                    words.append(w)
            if len(words) >= n_words:
                break
        if len(words) < 50:
            raise RuntimeError(f"only {len(words)} single-token words in the tokenizer")
        self.words = words
        self.overhead = len(self.encode([{"role": "system", "content": " a"},
                                         {"role": "user", "content": " a"}])) - 2

    def encode(self, messages: list[dict]) -> list[int]:
        return self.tok.encode_chat(messages)

    def text(self, seed: int, n: int) -> str:
        rng = random.Random(seed)
        return "".join(" " + rng.choice(self.words) for _ in range(n))

    def messages(self, system_tokens: int, user_parts: list[str], total_tokens: int | None,
                 system_seed: int) -> list[dict]:
        """System turn of ``system_tokens`` words (the shared prefix), user
        turn of the given parts; when ``total_tokens`` is given the last
        part is cut or grown by whole words until the prompt has exactly
        that many tokens."""
        system = self.text(system_seed, max(1, system_tokens))
        user = "".join(user_parts)
        msgs = [{"role": "system", "content": system}, {"role": "user", "content": user}]
        if total_tokens is None:
            return msgs
        for _ in range(4):
            diff = total_tokens - len(self.encode(msgs))
            if diff == 0:
                break
            if diff > 0:
                user += self.text(hash((system_seed, len(user))) & 0x7FFFFFFF, diff)
            else:
                user = " ".join(user.split(" ")[:diff])
            msgs[1]["content"] = user
        return msgs


def render_plan(plan: dict, traffic: dict, prompts: Prompts) -> None:
    """Put ``messages`` on every chat request of the plan, in place."""
    if plan["entry"] != "openai_chat":
        return
    sys_tokens = int(traffic.get("system_prefix_tokens", 0))
    sys_seed = int(traffic.get("system_seed", 11))
    blocks = traffic.get("blocks")
    block_text = {}
    if blocks:
        for b in range(int(blocks["pool"])):
            block_text[b] = prompts.text(1000 + b, int(blocks["block_tokens"]))
    reqs = plan["requests"] if plan["loop"] == "open" else \
        [r for c in plan["clients"] for r in c["requests"]]
    for r in reqs:
        if blocks:
            parts = [block_text[b] for b in r["blocks"]]
            question = int(blocks.get("question_tokens", 24))
            parts.append(prompts.text(r["words_seed"], question))
            total = prompts.overhead + sys_tokens + question \
                + len(r["blocks"]) * int(blocks["block_tokens"])
            r["prompt_tokens"] = total
            r["messages"] = prompts.messages(sys_tokens, parts, total, sys_seed)
        else:
            n_user = max(1, r["prompt_tokens"] - prompts.overhead - sys_tokens)
            r["messages"] = prompts.messages(sys_tokens, [prompts.text(r["words_seed"], n_user)],
                                             r["prompt_tokens"], sys_seed)
