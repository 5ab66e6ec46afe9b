"""The serve cell end to end on the CPU at a tiny size: a sound run is
correct, an altered token is caught, and the lower-precision control's
gap passes the limit."""
import pytest

import chipbench_tiny as T


@pytest.fixture(scope="module")
def base(tmp_path_factory):
    return T.make_base(tmp_path_factory.mktemp("tiny"))


def test_sound_run_is_correct(base):
    res = T.run_cell(base, T.SERVE, seed=2 ** 33 + 3)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] == 20
    assert set(res["metrics"]) == {"ttft_ms_p95", "peak_hbm_gib",
                                   "setup_s"}
    assert res["metrics"]["ttft_ms_p95"]["value"] > 0
    assert list(res)[-1] == "checks"


def test_altered_token_is_caught(base):
    res = T.run_cell(base, T.SERVE, seed=3, fault="altered_token")
    assert not res["correct"], res["checks"]


def test_lower_precision_control_fails(base):
    import json
    import run
    drv = run.load_module(T.HERE / "drivers" / "serve.py")
    cfg = json.loads((base / "configs" / "tiny-serve.json").read_text())
    traffic = json.loads((base / "traffic" / "serve.chat.json").read_text())
    reference = run.load_reference(base, cfg)
    engine = drv.build(cfg, reference, 4, drv.StampSink())
    drv.warm_up(engine, cfg["model"]["vocab"])
    prompts, max_new, arrivals = drv.make_requests(
        traffic, traffic["rate"], 1.0, 4, cfg["model"]["vocab"])
    reqs, _ = drv.serve(engine, prompts, max_new, arrivals)
    seqs = [(r.prompt, list(r.out_tokens))
            for r in drv.sample(reqs, 4, 30, 8)]
    gaps = drv.served_gaps(reference, engine.params, cfg["model"], seqs,
                           128, precision="fp8")
    assert max(gaps) > T.SERVE_LIMITS["served_logit_gap"], gaps


def test_traffic_is_the_same_work_for_every_seed():
    """Every seed offers the same schedule (lengths and arrival times);
    the seed draws the prompt tokens; the traffic's order seed reorders
    the schedule."""
    traffic = {"prompt": {"median": 256, "sigma": 0.8, "min": 32,
                          "max": 896},
               "output": {"median": 64, "sigma": 0.8, "min": 16,
                          "max": 256}, "max_total": 1024, "order_seed": 7}
    import run
    drv = run.load_module(T.HERE / "drivers" / "serve.py")
    a = drv.make_requests(traffic, 40.0, 10.0, 1, 50257)
    b = drv.make_requests(traffic, 40.0, 10.0, 2 ** 34 + 9, 50257)
    assert len(a[0]) == len(b[0]) == 400
    assert [len(p) for p in a[0]] == [len(p) for p in b[0]]
    assert a[1] == b[1] and a[2] == b[2]
    assert all(len(p) + o <= 1024 for p, o in zip(a[0], a[1]))
    assert any((p != q).any() for p, q in zip(a[0], b[0]))
    c = drv.make_requests(dict(traffic, order_seed=8), 40.0, 10.0, 1, 50257)
    assert sorted(map(len, a[0])) == sorted(map(len, c[0]))
    assert abs(a[2][-1] - c[2][-1]) < 1e-9
    assert [len(p) for p in a[0]] != [len(p) for p in c[0]]



def test_tpot_reader_leaves_out_the_profiled_window():
    """The per-layer TPOT tail is the p95 over the requests whose decode
    did not overlap the profiled window, a miss counting as infinite; with
    no such request it reads nothing."""
    import math
    from types import SimpleNamespace as NS
    import run
    reader = run.load_module(T.HERE / "metrics" / "tpot_ms_p95.serve.py")

    def ctx(spans, window=(10.0, 20.0), missed=0):
        """Requests of 11 tokens decoding over ``spans`` (start, seconds);
        ``missed`` more that never finished."""
        reqs = [NS(uid=i, rejected=False, done=True, out_tokens=[0] * 11)
                for i in range(len(spans))]
        reqs += [NS(uid=-1 - i, rejected=False, done=False, out_tokens=[])
                 for i in range(missed)]
        stamps = NS(first={i: 100.0 + a for i, (a, _) in enumerate(spans)},
                    done={i: 100.0 + a + d
                          for i, (a, d) in enumerate(spans)})
        return {"window": window, "t0": 100.0, "stamps": stamps,
                "requests": reqs}

    clean = [(0.0, 2.0 + 0.01 * i) for i in range(101)]
    assert reader.read(ctx(clean)) == pytest.approx(295.0)
    # decodes that overlap the window are left out, however slow
    assert reader.read(ctx(clean + [(9.0, 50.0)] * 20)) == \
        pytest.approx(295.0)
    assert reader.read(ctx(clean[:90], missed=11)) == math.inf
    assert reader.read(ctx([(12.0, 2.0)])) is None
