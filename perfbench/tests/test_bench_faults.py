"""The check fails what it must: each cell, rehearsed on the CPU with its
timed path broken underneath, prints `correct` false; and the control,
the plain reference in bfloat16, fails every cell's comparison.

The faults are planted in the program's XLA-native lowering (the one the
CPU runs) and its pool, where the answer is produced: an answer word
altered, half of the table's rows left out, and, for the table held
encrypted, the decryption skipped or the pool holding other words than
the ciphertext written at set-up. One more reads the client's bytes low,
as a program that read its answers past the counter would."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import control  # noqa: E402
from fvb import data as fdata  # noqa: E402
from fvb import harness  # noqa: E402
from fvb import reference as ref  # noqa: E402
from fvb import spec as fspec  # noqa: E402
from fvb import traffic as ftraffic  # noqa: E402

CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _altered(monkeypatch):
    from repro.kernels import ops as kops
    from repro.kernels import ref as kref
    select, group = kops.select_project_xla, kref.group_aggregate

    def bad_select(*a, **k):
        packed, count = select(*a, **k)
        return packed.at[0, 0].add(1.0), count

    def bad_group(*a, **k):
        res = dict(group(*a, **k))
        res["sum"] = res["sum"].at[:, 0].add(1.0)
        return res
    monkeypatch.setattr(kops, "select_project_xla", bad_select)
    monkeypatch.setattr(kref, "group_aggregate", bad_group)


def _half_rows(monkeypatch):
    import jax.numpy as jnp
    from repro.core import pool
    rows_of = pool.rows_of

    def half(paged, n_rows, row_words):
        rows = rows_of(paged, n_rows, row_words)
        keep = jnp.arange(n_rows)[:, None] < n_rows // 2
        return jnp.where(keep, rows, 0.0)
    monkeypatch.setattr(pool, "rows_of", half)


def _no_decrypt(monkeypatch):
    from repro.kernels import ref as kref
    monkeypatch.setattr(kref, "ctr_crypt", lambda data, *a, **k: data)


def _pool_not_as_written(monkeypatch):
    """The pool's words differ from what set-up wrote (as if the node kept
    another copy there): the at-rest check must see it."""
    import numpy as np
    from repro.core.pool import FarPool
    write = FarPool.write_table

    def altered(self, ft, words):
        words = np.array(words)
        words.reshape(-1).view(np.uint32)[-1] ^= 1
        write(self, ft, words)
    monkeypatch.setattr(FarPool, "write_table", altered)


def _wire_uncounted(monkeypatch):
    """The client's bytes read low (as if the program read its answers
    another way than the counter sees): the answer floor must catch it."""
    from fvb import served
    read = served.WireCount.read

    def low(self):
        conns, _ = read(self)
        return conns, 0
    monkeypatch.setattr(served.WireCount, "read", low)


FAULTS = {"altered": _altered, "half_rows": _half_rows,
          "no_decrypt": _no_decrypt,
          "pool_not_as_written": _pool_not_as_written,
          "wire_uncounted": _wire_uncounted}
AT_REST = ("no_decrypt", "pool_not_as_written")
CASES = [(c, f) for c in CELLS for f in FAULTS
         if f not in AT_REST or "enc" in c]


@pytest.fixture
def fresh_pipelines():
    from repro.core import pipeline
    pipeline.clear_cache()
    yield
    pipeline.clear_cache()


@pytest.mark.parametrize("cell,fault", CASES)
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch, capsys,
                                          fresh_pipelines):
    FAULTS[fault](monkeypatch)
    rc = harness.main(["--workload", cell, "--seed", "41", "--seconds",
                       "0.3", "--trace", "0", "--rehearse"])
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
    if fault == "pool_not_as_written":
        assert out["checks"]["pool_off_cipher"]["value"] == 1
    if fault == "wire_uncounted":
        assert out["checks"]["resp_below_answer"]["value"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_control_in_bfloat16_fails(cell):
    spec = fspec.load(cell)
    table = fdata.make_table(spec.config, 7, harness.REHEARSAL_ROWS)
    for inst in ftraffic.instances(spec.traffic, spec.config):
        want = ref.expect(table.words, table.index, inst.spec)
        got = ref.control_answer(table.words, table.index, inst.spec)
        nums = ref.compare(got, want)
        assert any(v > ref.LIMITS[k] for k, v in nums.items()), nums
        # and the reference passes itself
        same = ref.compare(_as_answer(want, table.words.shape), want)
        assert all(v == 0 for v in same.values()), same


@pytest.mark.parametrize("cell", CELLS)
def test_control_script_fails_on_every_seed(cell, capsys):
    assert control.main(["--workload", cell, "--seeds", "7,8,9",
                         "--rehearse"]) == 0
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    assert [x["seed"] for x in lines] == [7, 8, 9]
    assert all(x["control_fails"] for x in lines)


def _as_answer(want, shape):
    import numpy as np
    if want.kind == "groups":
        return {k: list(v) for k, v in want.groups.items()}
    rows = np.zeros(shape, np.float32)
    rows[: want.count][:, want.out_cols] = want.rows
    return want.count, rows
