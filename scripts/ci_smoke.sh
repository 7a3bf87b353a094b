#!/usr/bin/env bash
# CPU smoke job: tier-1 suite on the default (ref) backend, then the
# kernel + fused-selection tests again under Pallas interpret mode so the
# actual kernel bodies (not just the jnp oracles) are exercised on CPU.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# every stage runs on the CPU, simulating its meshes with host devices
export JAX_PLATFORMS=cpu

echo "== tier-1 (ref backend) =="
python -m pytest -x -q

echo "== kernel tests (REPRO_KERNEL_BACKEND=interpret) =="
REPRO_KERNEL_BACKEND=interpret python -m pytest -q \
    tests/test_kernels.py tests/test_fused_selection.py

echo "== megakernel parity (REPRO_KERNEL_BACKEND=interpret) =="
REPRO_KERNEL_BACKEND=interpret python -m pytest -q \
    tests/test_megakernel.py

echo "== objective registry sweep (conformance per registered spec) =="
# every registered objective must pass the generic conformance suite
# under interpret mode — registering a spec that fails conformance (or
# isn't exercised by the suite at all) fails CI here
OBJECTIVES=$(python -c "from repro.core.objective import registry; \
print(' '.join(registry()))")
echo "registry: ${OBJECTIVES}"
for obj in ${OBJECTIVES}; do
    # a registered name that matches NO conformance test is a failure in
    # its own right — check collection first so the diagnosis is
    # accurate (pytest would otherwise exit 5 on the empty selection)
    n=$(python -m pytest --collect-only -q \
        tests/test_objective_protocol.py -k "${obj}" 2>/dev/null \
        | grep -c "::" || true)
    if [ "${n}" -eq 0 ]; then
        echo "FAIL: objective '${obj}' is not covered by the conformance suite"
        exit 1
    fi
    echo "-- conformance: ${obj} (${n} tests) --"
    REPRO_KERNEL_BACKEND=interpret python -m pytest -q \
        tests/test_objective_protocol.py -k "${obj}" || {
        echo "FAIL: objective '${obj}' does not pass the conformance suite"
        exit 1
    }
done

echo "== streaming engine (REPRO_KERNEL_BACKEND=interpret) =="
REPRO_KERNEL_BACKEND=interpret python -m pytest -q \
    tests/test_streaming.py
python -m repro.launch.stream --smoke

echo "== measured-plan autotune (smoke grid, interpret) =="
# tiny tuner grid: must write the REPRO_AUTOTUNE_CACHE file, and a
# subsequent select_engine must REUSE the tuned entry (not re-derive the
# static heuristic plan)
AT_CACHE="$(mktemp -d)/plans.json"
REPRO_AUTOTUNE_CACHE="${AT_CACHE}" python -m repro.launch.autotune --smoke
test -s "${AT_CACHE}" || {
    echo "FAIL: autotune cache was not written"
    exit 1
}
REPRO_AUTOTUNE_CACHE="${AT_CACHE}" python - <<'PY'
from repro.kernels import plans, rules
entries = plans.load_autotune_cache()
assert entries, "autotune cache parsed empty"
key = plans.autotune_key(rules.DOT_MAX, 192, 192, 32, "interpret")
assert key in entries, (key, sorted(entries))
e = entries[key]
tuned = plans.select_engine(rules.DOT_MAX, 192, 192, 32,
                            requested="auto", backend="interpret")
if e["tier"] == "step":
    assert tuned.engine == "step", tuned
else:
    assert (tuned.tier, tuned.dtype) == (e["tier"], e["dtype"]), (tuned, e)
print(f"autotune cache reused: {key} -> {tuned.engine}/{tuned.dtype}")
PY

echo "== examples (interpret) =="
# the runnable docs: quickstart + the distributed summarization example
# must keep working against the current API surface (imports here rot
# silently otherwise — nothing else exercises the example scripts)
REPRO_KERNEL_BACKEND=interpret python examples/quickstart.py
REPRO_KERNEL_BACKEND=interpret python examples/data_summarization.py

echo "== fault tolerance (supervised runtime, 8-device mesh) =="
# level-replay bit-identity, the degraded-tree 0.95x quality band, and a
# supervised streaming pass — over a real 8-lane host mesh (faultrun sets
# xla_force_host_platform_device_count before importing jax). -m ""
# overrides pytest.ini's "not slow" default: this dedicated stage is
# where the slow subprocess mesh test runs
python -m pytest -q -m "" tests/test_fault_tolerance.py
python -m repro.launch.faultrun --smoke --mesh --lanes 8 --branching 2

echo "== serving engine (multi-tenant batched queries, interpret) =="
# subsystem tests, then the CLI gate: N mixed queries in → N bit-correct
# results out with ONE measured pallas dispatch per admitted batch, plus
# queue backpressure and a session-stream parity check
python -m pytest -q tests/test_serving.py
python -m repro.launch.qserve --smoke
# serving throughput artifact: the smoke sweep must emit BENCH_serve.json
python benchmarks/bench_serve.py --smoke
test -s benchmarks/BENCH_serve.json || {
    echo "FAIL: BENCH_serve.json was not written"
    exit 1
}

echo "== distributed scale (sharded tier + tree planner) =="
# shrunken per-device budget: the solo ladder and flat RandGreedi must
# both be refused so selection is forced through the sharded cross-device
# tier and the memory-model tree planner; the bench executes witness
# instances on a real 8-lane host mesh (bit-identical to solo greedy)
# and writes the memory-ceiling artifact. -m "" runs the slow subprocess
# mesh test excluded from the default tier-1 lane
python -m pytest -q -m "" tests/test_shard_scale.py
python benchmarks/bench_memory_limits.py --distributed --smoke
test -s benchmarks/BENCH_distributed.json || {
    echo "FAIL: BENCH_distributed.json was not written"
    exit 1
}
python - <<'PY'
import json
rec = json.load(open("benchmarks/BENCH_distributed.json"))
mx = rec["max_n"]
assert mx["planned"] > mx["solo"] >= mx["flat"], mx
assert all(w["bit_identical"] for w in rec["witnesses"]), rec["witnesses"]
assert any(w["shard"] > 1 for w in rec["witnesses"]), \
    "smoke run never exercised the sharded path"
assert rec["dispatch_contract"]["ok"], rec["dispatch_contract"]
print(f"distributed scale OK: planned N={mx['planned']} vs "
      f"solo N={mx['solo']}, flat N={mx['flat']}")
PY

echo "CI smoke OK"
