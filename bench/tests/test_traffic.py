"""The traffic generators: the same seed gives the same inputs, and every
seed gets the same set of sizes and gaps in another order."""
import json
from pathlib import Path

import numpy as np

import common
from drivers import serve

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"


def chat():
    return json.loads((TRAFFIC / "serve-chat.json").read_text())


def test_serve_schedule_same_seed_same_requests():
    a = serve.schedule(chat(), 2**31 + 11, 30.0, 32000)
    b = serve.schedule(chat(), 2**31 + 11, 30.0, 32000)
    assert len(a) == len(b)
    for (ta, pa, oa), (tb, pb, ob) in zip(a, b):
        assert ta == tb and oa == ob and np.array_equal(pa, pb)


def test_serve_schedule_seeds_share_arrivals_and_sizes():
    tf = chat()
    a = serve.schedule(tf, 3, 30.0, 32000)
    b = serve.schedule(tf, 2**33 + 3, 30.0, 32000)
    # one schedule of due times and sizes for every seed; other ids
    assert [(t, len(p), o) for t, p, o in a] == \
        [(t, len(p), o) for t, p, o in b]
    assert not np.array_equal(a[0][1], b[0][1])
    # the sizes are quantiles of the stated log-normals
    med = np.median([len(p) for _, p, _ in a])
    assert abs(med - tf["prompt_len"]["median"]) < 0.1 * med
    # the arrivals cover the window at the stated rate
    assert a[-1][0] > 30.0
    lo, hi = tf["prompt_len"]["min"], tf["prompt_len"]["max"]
    assert all(lo <= len(p) <= hi for _, p, _ in a)
    assert all(0 <= int(p.min()) and int(p.max()) < 32000 for _, p, _ in a)


def test_seed_keys_use_all_bits():
    import jax
    k1 = common.base_key(5)
    k2 = common.base_key(5 + 2**32)
    assert not np.array_equal(jax.random.key_data(k1),
                              jax.random.key_data(k2))


def test_weights_same_seed_same_values():
    from refs import decoder
    arch = {"n_layers": 1, "d_model": 16, "n_heads": 2, "n_kv_heads": 1,
            "d_ff": 32, "vocab_size": 256}
    specs = decoder.param_specs(arch)
    a = common.make_weights(specs, 2**31 + 1)
    b = common.make_weights(specs, 2**31 + 1)
    c = common.make_weights(specs, 2**31 + 2)
    for k in a:
        assert np.array_equal(a[k], b[k])
    assert not np.array_equal(a["embed"], c["embed"])
    assert float(a["final_norm"].min()) == 1.0
