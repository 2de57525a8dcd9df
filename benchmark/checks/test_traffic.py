"""The generators: the same seed gives the same inputs, and every seed the
same multiset of sizes."""

import numpy as np

from benchmark import traffic


def test_packed_pool_is_deterministic_and_rows_do_not_depend_on_the_seed():
    mix = traffic.load_mix("packed4k")
    a, rows_a = traffic.packed_pool(mix, 3, 32768)
    b, rows_b = traffic.packed_pool(mix, 3, 32768)
    c, rows_c = traffic.packed_pool(mix, 2**31 + 5, 32768)
    assert all((x["tokens"] == y["tokens"]).all() for x, y in zip(a, b))
    assert sorted(map(tuple, rows_a)) == sorted(map(tuple, rows_c))
    assert any((x["tokens"] != y["tokens"]).any() for x, y in zip(a, c))
    assert sum(int(x["loss_mask"].sum()) for x in a) == sum(int(x["loss_mask"].sum()) for x in c)
    for batch, k in zip(a, range(len(a))):
        assert batch["tokens"].shape == (mix["rows_per_chip"], mix["seq_len"])
        seg, pos = batch["segment_ids"], batch["positions"]
        assert ((seg == 0) == (batch["loss_mask"] == 0)).all()
        # positions restart at every document and no document is split
        starts = (pos == 0) & (seg > 0)
        assert (np.diff(seg, axis=1)[:, :][starts[:, 1:]] != 0).all()
        assert max(sum(r) for r in rows_a) <= mix["seq_len"]
