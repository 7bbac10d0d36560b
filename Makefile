# Offline-safe dev targets (no network, no extra installs).
PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test bench-smoke lint pimlint typecheck

# Tier-1 verify (ROADMAP.md). Hypothesis is optional; the suite runs
# deterministic fallback examples when it is absent.
test:
	$(PYTHON) -m pytest -x -q

# Kernel micro-bench in interpret mode + eager-vs-compiled executor
# comparison + the channel-overlap roofline report + the host-side
# scheduler/orchestration bench + the multi-tenant serving bench (grid,
# isolation, churn, hostile-admission legs) + the symbolic-analyzer cost
# trajectory; writes the bench-trajectory JSONs next to the repo.
bench-smoke:
	$(PYTHON) -m benchmarks.kernel_bench kernel_bench.json
	$(PYTHON) -m benchmarks.trace_replay
	$(PYTHON) -m benchmarks.roofline_report roofline_channels.json
	$(PYTHON) -m benchmarks.scheduler_bench scheduler_bench.json
	$(PYTHON) -m benchmarks.serve_bench serve_bench.json
	$(PYTHON) -m benchmarks.sem_bench sem_bench.json

# Syntax/bytecode check everywhere; upgrade to pyflakes when present.
lint:
	$(PYTHON) -m compileall -q src tests benchmarks examples chip_smoke.py
	@$(PYTHON) -c "import pyflakes" 2>/dev/null \
	  && $(PYTHON) -m pyflakes src tests benchmarks examples chip_smoke.py \
	  || echo "pyflakes not installed - compileall syntax check only"

# Static PIM-program verifier (DESIGN.md §12) + the semantic proof tier
# (§14): every golden known-bad fixture must flag its seeded hazard (incl.
# the PIM4xx symbolic findings and the pim405 equivalence proof), the
# clean fixtures must stay clean, the canonical workload generators must
# be error-free, and every canonical kernel must pass its fused-vs-unfused
# equivalence proof (the `sem:` report entries). Writes the
# machine-readable report for CI artifact upload.
pimlint:
	$(PYTHON) -m repro.core.pim.lint tests/fixtures/lint/*.trace \
	  --workloads --json pimlint_report.json

# mypy (lenient profile, mypy.ini) over the pim core; gated on
# availability like pyflakes — clean environments skip, CI installs it.
typecheck:
	@$(PYTHON) -c "import mypy" 2>/dev/null \
	  && $(PYTHON) -m mypy --config-file mypy.ini src/repro/core/pim \
	  || echo "mypy not installed - skipping typecheck"
