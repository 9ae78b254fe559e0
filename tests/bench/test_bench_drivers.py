"""Each traffic driver runs end to end on the CPU at a tiny size, with the
chip check skipped, and its run comes out correct with every metric its
cell reports."""
import numpy as np


def test_resident_gemv_cell(run_small):
    run, line = run_small("prim-resident-gemv", scale=2, clients=3)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "prim_requests_per_s",
                                    "prim_latency_p95_ms"}
    assert line["checks"]["gemv_err"]["value"] < 1e-6
    assert run.compiles_in_window == 0
    assert all(r.cache_hit for r in run.records)
    assert list(line)[-1] == "checks"


def test_stream_cols_cell_traced(run_small):
    run, line = run_small("prim-stream-cols", clients=2, trace=True)
    assert line["correct"] is True
    assert line["checks"]["wrong_answers"]["value"] == 0
    assert {"sched.queue_wait_ms", "pipeline.scatter_ms"} <= set(
        line["metrics"])
    assert set(run.facts["served"]) == {"VA", "HST"}
    assert line["device"]["window_s"] > 0
    assert not any(r.cache_hit for r in run.records)


def test_decode_engine_matches_greedy_generate_on_danube_smoke():
    """The program's decode engine against its own batched reference on
    the Danube3 smoke config, in a shared serving-mode session."""
    import jax

    from repro import pim
    from repro.configs.h2o_danube_3_4b import SMOKE
    from repro.launch.serve import greedy_generate
    from repro.models import transformer
    from repro.pim.decode import DecodeEngine
    from repro.runtime.elastic import carve_mesh
    params, specs = transformer.init(jax.random.PRNGKey(3), SMOKE)
    prompt = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (3, 5), 0,
                                           SMOKE.vocab))
    mesh = carve_mesh(jax.devices()[:1], model_parallel=1)
    want = np.asarray(greedy_generate(params, SMOKE, mesh, specs, prompt,
                                      max_new=6))
    with pim.session(banks=1, n_chunks=2) as s:
        got = DecodeEngine(params, SMOKE, session=s).generate(prompt, 6)
    np.testing.assert_array_equal(got, want)
