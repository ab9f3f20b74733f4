"""In-tree byte-level BPE (C++ core + Python front) vs the HuggingFace
``tokenizers`` library as ground truth: a ByteLevel BPE trained on a small
corpus, saved as tokenizer.json, loaded by both — ids must match exactly on
a battery of unicode-heavy inputs, and the native C++ merge loop must agree
with the pure-Python fallback.
"""

import pytest

tokenizers = pytest.importorskip("tokenizers")

from githubrepostorag_tpu.serving.bpe_native import NativeBPETokenizer  # noqa: E402
from githubrepostorag_tpu.serving.tokenizer import StreamingDetokenizer  # noqa: E402

CORPUS = [
    "def forward(self, x): return self.proj(x) + self.bias",
    "The quick brown fox jumps over the lazy dog. THE QUICK BROWN FOX!",
    "import numpy as np\nimport jax.numpy as jnp\n\n# comment line",
    "Cassandra vector store with SAI cosine index, batch size 128.",
    "don't we'll they've it's I'm you're he'd",
    "naïve café résumé — em-dash…ellipsis",
    "数字 123 和 456.789 与单词混合",
    "for i in range(100):\n    print(f\"{i:03d}\")\r\n\ttabbed",
    "emoji 🚀🔥 and symbols €£¥ ©®™",
    "   leading spaces and   multiple   gaps   ",
]

SPECIALS = ["<|endoftext|>", "<|im_start|>", "<|im_end|>"]

BATTERY = [
    "hello world",
    "def f(x): return x + 1  # increment",
    "don't stop",
    "multi\nline\n\ntext with\ttabs",
    "unicode: naïve café 数字 🚀",
    "numbers 42 and 3.14159 mixed with words",
    "",
    " ",
    "   spaced   out   ",
    "ALLCAPS lowercase MiXeD",
    "a",
    "🚀",
    "price: €99.99 (discount!)",
]


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    from tokenizers.implementations import ByteLevelBPETokenizer

    tok = ByteLevelBPETokenizer()
    tok.train_from_iterator(
        CORPUS * 4, vocab_size=600, min_frequency=1, special_tokens=SPECIALS
    )
    path = tmp_path_factory.mktemp("bpe") / "tokenizer.json"
    tok.save(str(path))
    hf = tokenizers.Tokenizer.from_file(str(path))
    return path, hf


@pytest.fixture(scope="module")
def native(trained):
    path, _ = trained
    return NativeBPETokenizer(path)


def test_native_backend_built(native):
    # the C++ library builds in this image (g++ present); if this fails the
    # fallback still works but the native core is what's under test
    assert native.backend == "native"


def test_encode_matches_hf_exactly(trained, native):
    _, hf = trained
    for text in BATTERY:
        assert native.encode(text) == hf.encode(text).ids, repr(text)


def test_encode_with_special_tokens(trained, native):
    _, hf = trained
    text = "<|im_start|>user\nhello world<|im_end|>\n<|im_start|>assistant\n"
    assert native.encode(text) == hf.encode(text).ids
    assert native.specials["<|im_end|>"] == native.eos_token_id


def test_python_fallback_matches_native(trained, native):
    path, _ = trained
    py = NativeBPETokenizer(path, use_native=False)
    assert py.backend == "python"
    for text in BATTERY:
        assert py.encode(text) == native.encode(text), repr(text)


def test_decode_roundtrip(trained, native):
    _, hf = trained
    for text in BATTERY:
        ids = native.encode(text)
        assert native.decode(ids) == hf.decode(ids, skip_special_tokens=True), repr(text)


def test_chat_template_and_streaming_detokenize(native):
    msgs = [{"role": "user", "content": "hi 🚀"}]
    ids = native.encode_chat(msgs)
    assert native.specials["<|im_start|>"] in ids
    # StreamingDetokenizer over the native tokenizer never emits half a
    # codepoint and reconstructs the prompt text (minus specials)
    sd = StreamingDetokenizer(native)
    out = "".join(sd.push(i) for i in ids) + sd.flush()
    assert out == native.decode(ids)
    assert "🚀" in out


def test_make_tokenizer_prefers_native(trained, tmp_path):
    import shutil

    from githubrepostorag_tpu.serving.tokenizer import make_tokenizer

    path, _ = trained
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    shutil.copy(path, ckpt / "tokenizer.json")
    tok = make_tokenizer(str(ckpt), backend="native")
    assert type(tok).__name__ == "NativeBPETokenizer"
    assert tok.encode("hello world")


def test_ignore_merges_and_nfc_normalizer_parity(trained, tmp_path):
    """Real checkpoints (Qwen2, Llama-3 family) set model.ignore_merges and
    a unicode normalizer; both must match HF exactly."""
    import json

    path, _ = trained
    spec = json.loads(path.read_text())
    spec["model"]["ignore_merges"] = True
    spec["normalizer"] = {"type": "NFC"}
    mod = tmp_path / "tokenizer.json"
    mod.write_text(json.dumps(spec))
    hf = tokenizers.Tokenizer.from_file(str(mod))
    ours = NativeBPETokenizer(mod)
    battery = BATTERY + [
        "café naïve",  # NFD input the normalizer must compose
        "the quick brown fox",  # words that are whole vocab entries
    ]
    for text in battery:
        assert ours.encode(text) == hf.encode(text).ids, repr(text)


def test_unsupported_normalizer_rejected(trained, tmp_path):
    import json

    path, _ = trained
    spec = json.loads(path.read_text())
    spec["normalizer"] = {"type": "Replace", "pattern": {"String": "x"}, "content": "y"}
    mod = tmp_path / "tokenizer.json"
    mod.write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="unsupported normalizer"):
        NativeBPETokenizer(mod)


def test_non_special_added_token_survives_decode(trained, tmp_path):
    import json

    path, _ = trained
    spec = json.loads(path.read_text())
    new_id = max(spec["model"]["vocab"].values()) + 1
    spec.setdefault("added_tokens", []).append({
        "id": new_id, "content": "JAXTPU", "special": False,
        "single_word": False, "lstrip": False, "rstrip": False,
        "normalized": False,
    })
    mod = tmp_path / "tokenizer.json"
    mod.write_text(json.dumps(spec))
    hf = tokenizers.Tokenizer.from_file(str(mod))
    ours = NativeBPETokenizer(mod)
    text = "run JAXTPU fast"
    ids = ours.encode(text)
    assert ids == hf.encode(text).ids
    assert new_id in ids
    # HF skip_special_tokens keeps non-special added tokens; so must we
    assert ours.decode(ids) == hf.decode(ids, skip_special_tokens=True)
    assert "JAXTPU" in ours.decode(ids)


def test_eos_from_tokenizer_config(trained, tmp_path):
    import json
    import shutil

    path, _ = trained
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    shutil.copy(path, ckpt / "tokenizer.json")
    (ckpt / "tokenizer_config.json").write_text(
        json.dumps({"eos_token": "<|endoftext|>"})
    )
    tok = NativeBPETokenizer(ckpt / "tokenizer.json")
    assert tok.eos_token_id == tok.specials["<|endoftext|>"]


def test_eos_refused_when_undeterminable(trained, tmp_path):
    """No config and no recognizable eos special: refuse rather than guess a
    stop token (make_tokenizer then falls back to transformers)."""
    import json

    path, _ = trained
    spec = json.loads(path.read_text())
    for t in spec.get("added_tokens", []):
        t["content"] = t["content"].replace("<|", "[").replace("|>", "]")
    vocab = spec["model"]["vocab"]
    for k in list(vocab):
        if k.startswith("<|"):
            vocab[k.replace("<|", "[").replace("|>", "]")] = vocab.pop(k)
    mod = tmp_path / "tokenizer.json"
    mod.write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="eos"):
        NativeBPETokenizer(mod)


def test_default_system_from_chat_template(trained, tmp_path):
    """from_checkpoint extracts the checkpoint's default system prompt from
    a Qwen2-style chat_template and injects it when chats carry no system
    turn — matching what transformers' template rendering would do."""
    import json
    import shutil

    path, _ = trained
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    shutil.copy(path, ckpt / "tokenizer.json")
    template = (
        "{%- if messages[0]['role'] == 'system' %}"
        "{{- '<|im_start|>system\\n' + messages[0]['content'] + '<|im_end|>\\n' }}"
        "{%- else %}"
        "{{- '<|im_start|>system\\nYou are a helpful assistant.<|im_end|>\\n' }}"
        "{%- endif %}"
    )
    (ckpt / "tokenizer_config.json").write_text(json.dumps(
        {"eos_token": "<|im_end|>", "chat_template": template}
    ))
    tok = NativeBPETokenizer.from_checkpoint(ckpt)
    assert tok.default_system == "You are a helpful assistant."
    rendered = tok.apply_chat_template([{"role": "user", "content": "hi"}])
    assert rendered.startswith("<|im_start|>system\nYou are a helpful assistant.")
    # explicit system turn wins
    rendered = tok.apply_chat_template(
        [{"role": "system", "content": "be terse"}, {"role": "user", "content": "hi"}]
    )
    assert "You are a helpful" not in rendered and "be terse" in rendered


def test_unrecognizable_chat_template_rejected(trained, tmp_path):
    import json
    import shutil

    path, _ = trained
    ckpt = tmp_path / "ckpt"
    ckpt.mkdir()
    shutil.copy(path, ckpt / "tokenizer.json")
    (ckpt / "tokenizer_config.json").write_text(json.dumps(
        {"chat_template": "{% for m in messages %}[{{m.role}}]{{m.content}}{% endfor %}"}
    ))
    with pytest.raises(ValueError, match="template"):
        NativeBPETokenizer.from_checkpoint(ckpt)


def test_add_prefix_space_rejected(trained, tmp_path):
    """RoBERTa-style add_prefix_space changes every first-word id; we don't
    implement it, so the loader must refuse (-> transformers fallback)."""
    import json

    path, _ = trained
    spec = json.loads(path.read_text())
    spec["pre_tokenizer"] = {"type": "ByteLevel", "add_prefix_space": True,
                             "trim_offsets": True, "use_regex": True}
    mod = tmp_path / "tokenizer.json"
    mod.write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="add_prefix_space"):
        NativeBPETokenizer(mod)


def test_unknown_pretokenizer_rejected(trained, tmp_path):
    import json

    path, _ = trained
    spec = json.loads(path.read_text())
    spec["pre_tokenizer"] = {"type": "Whitespace"}
    mod = tmp_path / "tokenizer.json"
    mod.write_text(json.dumps(spec))
    with pytest.raises(ValueError, match="pre_tokenizer"):
        NativeBPETokenizer(mod)


def test_long_input_stability(trained, native):
    _, hf = trained
    text = " ".join(CORPUS) * 8
    ids = native.encode(text)
    assert ids == hf.encode(text).ids
    assert native.decode(ids) == hf.decode(ids, skip_special_tokens=True)


@pytest.mark.parametrize("pattern", [None, r"[a-z]+", r"( ?[a-z]+)|\d"],
                         ids=["the-checkpoints-own", "skips-characters", "captures"])
def test_ascii_text_takes_one_native_call_and_agrees_with_the_merge_loop(trained, pattern):
    """ASCII text goes through ``_encode_ascii`` (one native call over the
    pattern's match boundaries, no list a segment); any other text, and a
    tokenizer with ``ignore_merges`` or without the library, keeps the general
    path.  Both must say what the pure-Python merge loop says, also where the
    pattern leaves characters out (each gap is a segment of its own) or has a
    capturing group (``findall`` then returns the group, not the match)."""
    import regex

    path, hf = trained
    fast, py = NativeBPETokenizer(path), NativeBPETokenizer(path, use_native=False)
    if pattern is not None:
        fast._re = py._re = regex.compile(pattern)
    taken = []
    inner = fast._encode_ascii
    fast._encode_ascii = lambda text: taken.append(text) or inner(text)
    ascii_texts = [t for t in BATTERY + CORPUS if t and t.isascii()]
    ascii_texts += ["\x00\x01 odd\r\n\tbytes  ", "x" * 700, " ".join(ascii_texts) * 6]
    for text in ascii_texts:
        assert fast.encode(text) == py.encode(text), repr(text[:40])
        if pattern is None:
            assert fast.encode(text) == hf.encode(text).ids, repr(text[:40])
    assert len(taken) >= len(ascii_texts)
    taken.clear()
    for text in (t for t in BATTERY if not t.isascii()):
        assert fast.encode(text) == py.encode(text), repr(text)
    assert taken == [] or all(t.isascii() for t in taken)  # a special token's neighbours may be
