"""Operation and byte counts against hand-worked values, and their
independence of the allocated cache length."""
from bench.lib import counts, model, serve, traffic, weights
from bench.tests import tiny

W = model.Widths(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, ff=16,
                 vocab=32, rope_theta=1e4, norm_eps=1e-5)


def test_hand_worked_counts():
    # per layer: q 8x8, k and v 8x4 each, o 8x8, three 8x16 MLP matrices
    assert counts.layer_matmul_params(W) == 64 + 64 + 64 + 384
    assert counts.matmul_params(W) == 2 * 576 + 8 * 32
    # 2 sequences attending to 10 positions in all
    flops, nbytes = counts.decode_step(W, 2, 10)
    assert flops == 2 * 1408 * 2 + 4 * 2 * 2 * 4 * 10
    # weights but the embedding table (norm scales: 2 per layer + final),
    # 2 embedding rows, 10 K/V rows of 2 layers x 1 head x 4 x 2 (k, v)
    assert nbytes == (1408 + 5 * 8) * 2 + 2 * 8 * 2 + 32 * 10
    # prompt of 3: layers, causal attention over 1+2+3 positions, one
    # vocabulary row
    assert counts.prefill(W, 3) == 2 * 2 * 576 * 3 + 4 * 2 * 2 * 4 * 6 \
        + 2 * 8 * 32
    assert counts.decode_step(W, 0, 0) == (0, 0)


def _steps(max_seq: int) -> list:
    cell = tiny.cell("backlog")
    mix = {**cell.mix, "server": {**cell.mix["server"], "max_seq": max_seq}}
    w = model.widths(cell.config)
    srv = serve.make_server(model.program_config("tiny", cell.config),
                            weights.build(7, w), mix)
    serve.warm_up(srv, mix, w.vocab)
    reqs = traffic.requests(mix, w.vocab, 1.0, 7)
    win = serve.serve(srv, reqs, mix, 1.0, serve.spans(False))
    return win.steps


def test_counts_do_not_depend_on_the_allocated_cache():
    """The same traffic through servers with 64 and 128 positions per slot:
    the steps see the same sequences at the same positions, so the counts
    behind every roofline and MFU share agree."""
    a, b = _steps(64), _steps(128)
    n = min(len(a), len(b))
    assert n > 10
    assert a[:n] == b[:n]
    w = model.widths(tiny.CONFIG)
    assert [counts.decode_step(w, *s) for s in a[:n]] == \
        [counts.decode_step(w, *s) for s in b[:n]]
