"""Config: reference env-var names resolve into the unified Settings."""

from githubrepostorag_tpu.config import Settings, get_settings, reload_settings


def test_defaults_match_reference():
    s = Settings()
    assert s.max_rag_attempts == 3
    assert s.min_source_nodes == 1
    assert s.router_top_k == 5
    assert s.embed_dim == 384
    assert s.qwen_max_output == 4096
    assert s.sse_ping_seconds == 15
    assert s.context_window == 11712
    assert s.embeddings_table_chunk == "embeddings"
    assert s.embeddings_table_catalog == "embeddings_catalog"
    assert s.prefill_token_budget == 0  # default: padded prefill dispatch


def test_env_overrides(monkeypatch):
    monkeypatch.setenv("MAX_RAG_ATTEMPTS", "7")
    monkeypatch.setenv("EMBEDDINGS_TABLE", "alt_embeddings")
    monkeypatch.setenv("DEV_MODE", "true")
    monkeypatch.setenv("PREFILL_WIDTHS", "2")  # gone: the width ladder is derived
    monkeypatch.setenv("PREFILL_TOKEN_BUDGET", "2048")
    s = reload_settings()
    assert s.max_rag_attempts == 7
    assert s.embeddings_table_chunk == "alt_embeddings"
    assert s.dev_force_standalone is True
    assert not hasattr(s, "prefill_widths")
    assert s.prefill_token_budget == 2048


def test_scope_tables_cover_all_five_levels():
    tables = get_settings().scope_tables
    assert set(tables) == {"catalog", "repo", "module", "file", "chunk"}


def test_bad_env_int_falls_back(monkeypatch):
    monkeypatch.setenv("ROUTER_TOP_K", "not-a-number")
    s = reload_settings()
    assert s.router_top_k == 5


def test_quantize_weights_values(monkeypatch):
    from githubrepostorag_tpu.config import reload_settings

    for raw, want in [("int4", 4), ("int8", 8), ("true", 8), ("4", 4),
                      ("", 0), ("false", 0)]:
        monkeypatch.setenv("QUANTIZE_WEIGHTS", raw)
        assert reload_settings().quantize_weights == want, raw


def test_quantize_weights_typo_raises(monkeypatch):
    import pytest

    from githubrepostorag_tpu.config import reload_settings

    monkeypatch.setenv("QUANTIZE_WEIGHTS", "in8")
    with pytest.raises(ValueError, match="QUANTIZE_WEIGHTS"):
        reload_settings()
    monkeypatch.setenv("QUANTIZE_WEIGHTS", "int8")
    reload_settings()


def test_no_setting_bounds_an_experts_capacity(monkeypatch):
    """The dispatch is dropless (models/moe.dropless_experts): the knobs of
    the dropping one are gone, and setting their variables changes nothing."""
    from githubrepostorag_tpu.config import reload_settings

    monkeypatch.setenv("MOE_CAPACITY_FACTOR", "1.25")
    monkeypatch.setenv("MOE_DROP_STATS", "1")
    s = reload_settings()
    assert not hasattr(s, "moe_capacity_factor")
    assert not any("moe" in name for name in vars(s))
