PYTHON ?= python

.PHONY: proto native test bench-check lint-check clean

proto:
	protoc --proto_path=seldon_core_tpu/proto \
	       --python_out=seldon_core_tpu/proto \
	       seldon_core_tpu/proto/prediction.proto

# no -march=native: the tree is copied to other hosts (the chip tool), and a
# binary built for this CPU could fault there.  NATIVE_OUT lets the tests
# build into a temp dir instead of leaving a binary in the package.
NATIVE_OUT ?= seldon_core_tpu/_native

native:
	mkdir -p $(NATIVE_OUT)
	g++ -O3 -shared -fPIC -o $(NATIVE_OUT)/libsctcodec.so csrc/codec.cpp

# tier-1, as the PR check selects and spreads it (ROADMAP.md "Tier-1 verify")
test:
	$(PYTHON) -m pytest tests/ -q -m 'not slow' -p xdist -n 6 --dist loadfile

# the benchmark's own tests (reductions, judges, arrival processes, the
# cells' declarations): the harness is outside the package and outside tests/
bench-check:
	$(PYTHON) -m pytest benchmark/tests -q

# invariant-aware static analysis (docs/STATIC_ANALYSIS.md): host-sync,
# program-key, pairing, env-registry, async-discipline, test-hygiene,
# ring-growth.
# Stdlib-only (no jax), so the bare CI lint job runs it without installs;
# fails on any finding not in sctlint-baseline.json and on stale
# baseline entries
lint-check:
	$(PYTHON) -m seldon_core_tpu.tools.sctlint

clean:
	find . -name __pycache__ -type d -exec rm -rf {} +
