# Tier-1 verify and friends. `make test` is the command the driver runs;
# keeping it here means an environment failure mode (missing dev dep,
# wrong PYTHONPATH) surfaces as a red make target, not a silent skip.

PYTHON ?= python
export PYTHONPATH := src$(if $(PYTHONPATH),:$(PYTHONPATH))

.PHONY: test test-data test-delivery test-state test-transport test-obs test-groups test-replication test-codec test-analyze analyze lint examples deps-check

test:           ## tier-1: invariant analyzer first, then the full suite, stop at first failure
	$(PYTHON) -m tools.analyze src/ tests/
	$(PYTHON) -m pytest -x -q

analyze:        ## project invariant analyzer (docs/static_analysis.md); exit 1 on findings
	$(PYTHON) -m tools.analyze src/ tests/

lint: analyze   ## alias for analyze

test-analyze:   ## the analyzer's own suite + the lock-order harness unit tests
	$(PYTHON) -m pytest -q tests/test_analyze.py tests/test_locktrace.py

test-data:      ## just the data subsystem (sources/sinks/windows/broker/durability)
	$(PYTHON) -m pytest -q tests/test_data_sources.py tests/test_data_sinks.py \
	    tests/test_data_window.py tests/test_broker_dstream.py \
	    tests/test_broker_parity.py tests/test_durable_log.py \
	    tests/test_window_state.py

test-state:     ## restart-safe windowed state (stores, atomic checkpoint, SIGKILL crash)
	$(PYTHON) -m pytest -q tests/test_window_state.py tests/test_data_window.py \
	    tests/test_broker_dstream.py

test-delivery:  ## parallel sink delivery chaos suite + lag-driven elastic ingest
	$(PYTHON) -m pytest -q tests/test_delivery.py tests/test_elastic_ingest.py

test-transport: ## socket broker transport (framing properties, reconnect, cross-process)
	$(PYTHON) -m pytest -q tests/test_transport.py tests/test_transport_frames.py \
	    tests/test_broker_parity.py

test-obs:       ## telemetry: metrics registry, trace spans, observability endpoint
	$(PYTHON) -m pytest -q tests/test_metrics.py tests/test_obs_server.py

test-groups:    ## consumer groups: assignor properties, fencing, partition-handoff chaos suite
	$(PYTHON) -m pytest -q tests/test_groups.py tests/test_broker_parity.py

test-replication: ## broker HA: follower replication, failover promotion, epoch fencing
	$(PYTHON) -m pytest -q tests/test_replication.py tests/test_broker_parity.py \
	    tests/test_durable_log.py

test-codec:     ## per-topic payload codecs: int8/zlib roundtrips, wire refusal, parity matrix
	$(PYTHON) -m pytest -q tests/test_codec.py tests/test_broker_parity.py

examples:       ## fast end-to-end example runs
	$(PYTHON) examples/ptycho_pipeline.py --fast
	$(PYTHON) examples/tomo_pipeline.py --nray 32 --nslice 16
	$(PYTHON) examples/remote_ingest.py --frames 48
	$(PYTHON) examples/ha_failover.py --batches 40

deps-check:     ## verify runtime imports resolve (no installs) + docs links
	$(PYTHON) -c "import jax, numpy, scipy; print('runtime deps ok')"
	-$(PYTHON) -c "import hypothesis; print('hypothesis ok')"
	$(PYTHON) tools/check_docs_links.py
