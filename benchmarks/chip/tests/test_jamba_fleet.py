"""The ``jamba2-fleet`` and ``100m-train-dp4`` cells on the CPU at small
sizes: both resolve from the manifest; the fleet over real layer
programs is ``correct`` as the program is and not for the control; a
program without the architecture fails cleanly; and the three new
readers on synthetic traces."""
import pytest

from chip import harness as H
from chip import run as R
from chip.drivers import fleet_layers as FLL
from chip.metrics import collective_share as CS
from chip.metrics import ingest_us_per_search as IU
from chip.metrics import truncation_share as TS

TINY_ARCH = {"hidden_size": 64, "num_attention_heads": 4,
             "num_key_value_heads": 2, "intermediate_size": 96,
             "num_experts": 4, "mamba_d_state": 8, "mamba_dt_rank": 4}
TINY_100M = {"vocab_size": 4096, "max_seq": 256, "embed_dim": 16,
             "conv_channels": [16] * 6, "fc_dims": [32, 16]}
SMALL_FLEET = {"workers": 2, "batches": [1, 2], "lengths": [16, 48],
               "warm_batches": [1], "warm_lengths": [8], "repeats": 8,
               "check_rows": 100,
               "layer_check": {"prefill": [1, 40], "decode_cache": 64,
                               "block": 16}}


def _cell():
    cell = H.resolve("jamba2-fleet")
    cell.config["architecture"] = dict(cell.config["architecture"],
                                       **TINY_ARCH)
    cell.config["num_experts"] = 2
    cell.config["cost_model"] = dict(cell.config["cost_model"], **TINY_100M)
    cell.traffic.update(SMALL_FLEET)
    return cell


def _run(hooks=None, seed=2**31 + 11):
    """The result, every reading, and the driver's outcome (its layers)."""
    args = R.parse(["--workload", "jamba2-fleet", "--seed", str(seed),
                    "--seconds", "2", "--trace", "0"])
    read, kept = {}, []
    real = FLL.run

    def run(*a, **k):
        kept.append(real(*a, **k))
        return kept[-1]
    FLL.run = run
    try:
        res = R.execute(args, require_chip=False, cell=_cell(),
                        t_process=H.now(), hooks=hooks or {},
                        readings=read)
    finally:
        FLL.run = real
    return res, read, kept[0]


def test_both_new_cells_resolve():
    fleet = H.resolve("jamba2-fleet")
    assert fleet.driver == "fleet_layers" and fleet.workload["chips"] == 1
    assert fleet.config["cost_model"]["name"] == "costmodel-100m"
    assert fleet.config["architecture"]["num_experts"] == 16
    assert {m["name"] for m in fleet.end_to_end} == {
        "setup_s", "fleet_cands_per_s"}
    dp4 = H.resolve("100m-train-dp4")
    assert dp4.driver == "train_stream_dp4" and dp4.workload["chips"] == 4
    assert dp4.traffic["mesh_data"] == 4
    assert {m["name"] for m in dp4.per_layer} == {
        "collective_share.dp4", "device_idle_share.dp4",
        "train_pad_share.dp4", "step_pad_share.dp4",
        "idle_input_share.dp4", "train_mesh_mfu.dp4"}
    # every layer the fleet runs has its metric in the cell
    assert {m["name"].split(".")[0] for m in fleet.per_layer} == {
        "batch_occupancy", "featurize_us_per_req", "cache_hit_rate",
        "search_self_share", "forward_roofline", "serve_mfu",
        "device_idle_share", "queue_wait_us_per_req", "caller_wait_share",
        "idle_queued_share", "truncation_share", "ingest_us_per_search"}


@pytest.fixture(scope="module")
def sound():
    return _run()


def test_fleet_layers_feed_the_host_readers(sound):
    """The host-side readers of the cell read the run's own layers."""
    _, _, out = sound
    ctx = {"outcome": out, "device": {"kind": "TPU v5 lite", "count": 1}}
    for name in ("batch_occupancy", "featurize_us_per_req",
                 "cache_hit_rate", "search_self_share", "serve_mfu"):
        v = H.metric_reader(name)(ctx, {})
        assert v is not None and v >= 0, name
    assert 0 < H.metric_reader("search_self_share")(ctx, {}) < 100


def test_train_mesh_mfu_counts_every_chip():
    class Out:
        layers = {"steps": 3, "model_flops": 4e14}
        window_s = 2.0
    ctx = {"outcome": Out, "device": {"kind": "TPU v5 lite", "count": 4}}
    one = H.metric_reader("train_mfu")(ctx, {})
    assert H.metric_reader("train_mesh_mfu")(ctx, {}) == \
        pytest.approx(one / 4)


def test_fleet_over_layer_programs_is_correct(sound):
    res, read, _ = sound
    assert res["correct"], res["checks"]
    assert res["metrics"]["fleet_cands_per_s"]["value"] > 0
    assert read["frontdoor_lossy_ops"] == 0
    # the limit sits between the program and the controls
    lim = res["checks"]["layer_gap"]["limit"]
    assert read["layer_gap"] < lim < read["layer_gap_float8"]
    assert read["layer_gap_no_d"] > lim


def test_fleet_control_fails():
    res, _, _ = _run({"control": True}, seed=2**31 + 12)
    failed = {k for k, c in res["checks"].items()
              if not c["value"] <= c["limit"]}
    assert not res["correct"] and {"row_gap_mean", "layer_gap"} <= failed


def test_a_program_without_the_architecture_fails_cleanly(monkeypatch):
    from repro import configs
    monkeypatch.delitem(configs.ARCHS, "jamba2-mini")
    with pytest.raises(H.BenchError):
        FLL.system_arch(_cell().config)


# ------------------------------------------------------------ readers
def _span(name, t0, dur, **args):
    e = {"plane": "/host:CPU", "line": "python3", "tid": 0, "name": name,
         "t0": float(t0), "dur": float(dur)}
    if args:
        e["args"] = args
    return e


def test_truncation_share_reads_featurize_spans():
    ev = [_span("bench.window", 100, 1000),
          _span("server.featurize", 50, 5, hit=0, truncated=1),  # before
          _span("server.featurize", 110, 5, hit=0, truncated=1),
          _span("server.featurize", 120, 5, hit=0, truncated=0),
          _span("server.featurize", 130, 5, hit=1, truncated=0),
          _span("server.featurize", 140, 5, hit=0, truncated=0),
          _span("server.featurize", 150, 5, hit=0, truncated=1)]
    assert TS.truncation_share(ev) == pytest.approx(50.0)
    old = [dict(e, args={"hit": 0}) if "args" in e else e for e in ev]
    assert TS.truncation_share(old) is None


def test_ingest_us_per_search_reads_parse_spans():
    ev = [_span("bench.window", 0, 10_000_000),
          _span("bench.search", 10, 3_000_000),
          _span("frontdoor.parse", 20, 400_000),
          _span("bench.search", 4_000_000, 3_000_000),
          _span("frontdoor.parse", 4_000_010, 600_000),
          _span("frontdoor.parse", 20_000_000, 900_000)]     # after
    assert IU.ingest_us_per_search(ev) == pytest.approx(500.0)
    assert IU.ingest_us_per_search(ev[:2]) is None


def test_collective_share_sums_collective_ops():
    ops = {"fusion.3": 0.5, "all-reduce.1": 0.1, "all-reduce-start": 0.05,
           "reduce-scatter.2": 0.05, "all-gather-done.4": 0.1,
           "convolution": 0.2}
    assert CS.collective_share(ops, 1.0) == pytest.approx(30.0)
    assert CS.collective_share({"fusion": 1.0}, 1.0) == 0.0
    assert CS.collective_share(ops, 0.0) is None
