# Developer entrypoints (reference: Makefile — env create + per-component
# pytest; here one package, one suite, plus native build / deploy).

.PHONY: all native test test-fast serve lint lint-diff lint-baseline image deploy clean

all: native test

native:
	$(MAKE) -C native

test: native
	python -m pytest tests/ -q

test-fast:
	python -m pytest tests/ -q -m "not slow"

# tpulint: in-tree static analysis for JAX trace-safety, host-sync,
# async-race hazards, the whole-program WPA pass, and the SHP
# shape-provenance taint pass (fails on any unsuppressed finding not in
# the committed baseline; fixtures under tests/lint_fixtures are the rule
# corpus, not production code)
lint:
	python -m tools.tpulint githubrepostorag_tpu tests \
		--exclude tests/lint_fixtures --baseline tools/tpulint/baseline.json

# fast pre-push lint: only files changed vs BASE (default HEAD) plus every
# file that transitively imports them; the whole-program graph still spans
# the full tree, so cross-module SHP/WPA facts stay exact
BASE ?= HEAD
lint-diff:
	python -m tools.tpulint githubrepostorag_tpu tests \
		--exclude tests/lint_fixtures --baseline tools/tpulint/baseline.json \
		--diff $(BASE)

# regenerate the baseline after an intentional change (new rule rollout);
# the committed baseline is expected to stay empty — prefer a justified
# `# tpulint: disable=RULE -- why` suppression over baselining debt
lint-baseline:
	python -m tools.tpulint githubrepostorag_tpu tests \
		--exclude tests/lint_fixtures --write-baseline tools/tpulint/baseline.json

serve:
	python -m githubrepostorag_tpu.api --port 8080

image:
	docker build -t rag-tpu:latest -f docker/Dockerfile .

deploy:
	./start.sh

clean:
	$(MAKE) -C native clean || true
	find . -name __pycache__ -type d -exec rm -rf {} + 2>/dev/null || true
