# Developer targets (reference Makefile:25-72 test split analog).

.PHONY: test test_fast test_slow test_core test_big_modeling test_cli test_examples \
        test_multiprocess test_kernels native bench chaos quality lint-json

test:
	python -m pytest tests/ -q

# the developer loop: everything not marked slow (< 2 min; see tests/conftest.py)
test_fast:
	python -m pytest tests/ -q -m "not slow"

test_slow:
	python -m pytest tests/ -q -m "slow"

# split targets for CI sharding
test_core:
	python -m pytest tests/ -q --ignore=tests/test_examples.py \
	    --ignore=tests/test_big_modeling.py --ignore=tests/test_cli.py \
	    --ignore=tests/test_multiprocess.py --ignore=tests/test_flash_attention.py \
	    --ignore=tests/test_ring_attention.py --ignore=tests/test_fp8.py \
	    --ignore=tests/test_quantization.py

test_big_modeling:
	python -m pytest tests/test_big_modeling.py tests/test_quantization.py -q

test_cli:
	python -m pytest tests/test_cli.py -q

test_examples:
	python -m pytest tests/test_examples.py -q

test_multiprocess:
	python -m pytest tests/test_multiprocess.py -q

test_kernels:
	python -m pytest tests/test_flash_attention.py tests/test_ring_attention.py tests/test_fp8.py -q

native:
	$(MAKE) -C accelerate_tpu/native

bench:
	python bench.py

# fault-tolerance gate: the deterministic fault-injection test suite (replica
# kill -> token-identical replay, page exhaustion, deadlines, disconnects)
chaos:
	python -m pytest tests/test_fault_tolerance.py -q

# one process, one AST load per file, all ten rules (tools/atpu_lint/rules/);
# the lint surface includes the linter itself (docs/development/static-analysis.md)
quality:
	python -m compileall -q accelerate_tpu
	python -m tools.atpu_lint accelerate_tpu tests tools bench.py

# machine-readable report for CI artifacts / editor integration
lint-json:
	@python -m tools.atpu_lint accelerate_tpu tests tools bench.py --format json
