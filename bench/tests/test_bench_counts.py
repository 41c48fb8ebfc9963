"""Each configuration's FLOPs per round and each roofline metric's bytes,
against hand counts from the published shapes."""
import json
import pathlib

import pytest

from bench import run, traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _cfg(name):
    return json.loads((ROOT / f"bench/configs/{name}.json").read_text())


def test_qwen_params_and_flops_by_hand():
    from bench.reference import decoder_lm
    m = decoder_lm.from_config(_cfg("qwen1.5-0.5b"))
    embed = 151936 * 1024
    per_layer = 4 * 1024 * 1024 + 3 * 1024 + 3 * 1024 * 2816 + 2 * 1024
    n = embed + 24 * per_layer + 1024
    assert n == 463_987_712
    assert decoder_lm.n_params(m) == n
    per_token = 6 * n + 12 * 24 * 512 * 16 * 64
    assert decoder_lm.train_flops_per_sample(m, 512) == per_token * 512
    cell = run.load_cell("qwen05b.p2-fedavg")
    # K=4 clients x 4 local steps x batch 4 sequences of 512 tokens
    assert traffic.samples_per_round(cell.traffic) == 64
    assert 64 * per_token * 512 == pytest.approx(9.617e13, rel=1e-3)


def test_lenet_flops_by_hand():
    from bench.reference import lenet5
    m = lenet5.from_config(_cfg("lenet5-cifar10"))
    fwd = (2 * 32 * 32 * 6 * 5 * 5 * 3 + 2 * 16 * 16 * 16 * 5 * 5 * 6
           + 2 * (16 * 8 * 8 * 120 + 120 * 84 + 84 * 10))
    assert fwd == 2_418_000
    assert lenet5.train_flops_per_sample(m) == 3 * fwd
    assert lenet5.n_params(m) == (5 * 5 * 3 * 6 + 6 + 5 * 5 * 6 * 16 + 16
                                  + 1024 * 120 + 120 + 120 * 84 + 84
                                  + 84 * 10 + 10)
    cell = run.load_cell("lenet5.p2-fedavg")
    # K = 10% of 100 clients, 15 local steps of 32 images
    assert traffic.clients_per_round(cell.traffic) == 10
    assert traffic.samples_per_round(cell.traffic) == 4800


def test_peaks_are_keyed_by_device_kind():
    from bench import peaks
    assert peaks.peaks_of("TPU v5 lite").hbm_bw == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks_of("TPU v99")


DATA = ROOT / "bench" / "tests" / "data"
# the roofline readers' readings on the recorded one-chip traces before
# they took the chip's share: one call of LeNet-5's fused_local_step
ONE_CHIP = {"trace_lenet_v5e": 90.54878905894708,
            "trace_scopes_v5e": 90.67159483072685}


def _roofline_ctx(ops, window, params, chips, param_bytes):
    import types
    from bench import peaks, xtrace
    return types.SimpleNamespace(
        trace=xtrace.Trace({0: ops}, []), window=window, rounds=1,
        clients_per_round=1, local_steps=1, param_bytes=param_bytes,
        params=params, chip_params=run.chip_params(params, chips),
        chips=chips, peaks=peaks.PEAKS["TPU v5 lite"])


def _reader(name):
    return run.load_module([run.BENCH], "metrics", name).read


@pytest.mark.parametrize("trace", sorted(ONE_CHIP))
def test_rooflines_on_one_chip_read_as_before(trace):
    from bench import xtrace
    from bench.reference import lenet5
    fx = json.loads((DATA / f"{trace}.json").read_text())
    ops = [xtrace.Event(*o) for o in fx["device_ops"]]
    n = lenet5.n_params(lenet5.from_config(_cfg("lenet5-cifar10")))
    one = _roofline_ctx(ops, tuple(fx["window"]), n, 1, 4)
    assert one.chip_params == n
    assert _reader("local_step_roofline")(one) == pytest.approx(
        ONE_CHIP[trace], rel=1e-12)
    assert _reader("delta_accum_roofline")(one) is None
    # the same events on a chip of four hold a quarter of the work
    four = _roofline_ctx(ops, tuple(fx["window"]), n, 4, 4)
    assert four.chip_params == -(-n // 4)
    assert _reader("local_step_roofline")(four) == pytest.approx(
        ONE_CHIP[trace] * four.chip_params / n, rel=1e-12)


@pytest.mark.parametrize("chips", [1, 4])
def test_delta_accum_roofline_by_hand(chips):
    from bench import xtrace
    n = 463_987_712                     # qwen1.5-0.5b, bf16 params
    ops = [xtrace.Event("%fused_delta_accum.3 = f32[...] custom-call(",
                        1e6, 8.5e6)]
    ctx = _roofline_ctx(ops, (0.0, 2e7), n, chips, 2)
    # f32 delta read and written, end and round params read at 2 bytes
    least = (4 + 2 + 2 + 4) * -(-n // chips) / 819e9
    assert _reader("delta_accum_roofline")(ctx) == pytest.approx(
        100 * least / 8.5e-3)
