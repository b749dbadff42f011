"""CPU rehearsal helpers: the cells at a tiny size, and the harness with
its look for a chip steered to the CPU."""
import jax
import pytest

from bench import peaks
from bench import run as R
from bench import trace as tr

# the smoke widths of the LM, a few hundred SVM rows
TINY_CONFIG = {
    "smollm-360m": {"config": dict(
        hidden_size=96, intermediate_size=256, num_hidden_layers=2,
        num_attention_heads=3, num_key_value_heads=1, head_dim=32,
        vocab_size=512)},
    "svm-epsilon": {"samples": 500, "features": 22},
}
TINY_TRAFFIC = {
    "smollm-360m.s4096": {"seq_len": 64},
    "svm-epsilon.k4.b64": {"epochs_per_job": 2, "block_per_worker": 8},
}
# the SVM cell's limit is set from chip readings at the cell's own size;
# at the test size it is set from CPU readings: program w_rel_l2 0 on one
# device and at most 7.6e-8 on four, control 2.4e-6 to 2.7e-6, faults
# 0.17 and more
TINY_CHECK = {
    "svm-epsilon.k4.b64": {"w_rel_l2": 5e-7},
}


def shrink(path_parts, data):
    """The tiny version of a configuration or cell file."""
    kind, name = path_parts[-2], path_parts[-1][:-len(".json")]
    if kind == "configs":
        for k, v in TINY_CONFIG[name].items():
            data[k] = {**data[k], **v} if isinstance(v, dict) else v
    if kind == "workloads":
        data["traffic_params"].update(TINY_TRAFFIC[name])
        data["check"] = {**data["check"], **TINY_CHECK.get(name, {})}
        data["chips"] = 1                  # one CPU device here
    return data


def cpu_trace(path):
    """The CPU runs XLA's operations on host threads: read those as the
    one device's operations, so that the reduction has something to
    read."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans = [], []
    for plane in pd.planes:
        for line in plane.lines:
            for e in line.events:
                lo, hi = int(e.start_ns), int(e.start_ns + e.duration_ns)
                if e.name.startswith(tr.SPAN_PREFIX):
                    spans.append((e.name, lo, hi))
                elif any(k == "hlo_op" for k, _ in e.stats):
                    ops.append((e.name, lo, hi))
    return tr.Trace({"/device:CPU:0": tr.leaf_ops(ops)}, sorted(spans))


@pytest.fixture
def harness(monkeypatch):
    real = R.load_json
    monkeypatch.setattr(R, "load_json", lambda *p: shrink(p, real(*p))
                        if p[-2] in ("configs", "workloads") else real(*p))
    monkeypatch.setattr(R, "require_devices",
                        lambda chips: jax.devices()[:1])
    import repro.launch.cache
    monkeypatch.setattr(repro.launch.cache, "use_compile_cache",
                        lambda: "off")
    monkeypatch.setattr(tr, "load", cpu_trace)
    # a table of this host's peaks does not exist; any kind's will do to
    # exercise the readers
    real_peaks = peaks.lookup
    monkeypatch.setattr(peaks, "lookup",
                        lambda kind: real_peaks("TPU v5 lite"))
    return R
