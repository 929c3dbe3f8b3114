"""The benchmark's vectorised generators against the program's per-row
ones: same tables, columns, dtypes and key ranges, truth rates within
sampling error, and the same prompt templates."""
import math

import numpy as np
import pytest

from perfbench import data as datagen
from perfbench.data import ecommerce
from repro.data import schemas as S
from repro.engine import Database

CASES = [("ecommerce", ecommerce, S.make_ecommerce, 3.0)]


def _load(schema, scale):
    d = datagen.generate(schema, 2**31 + 77, scale)
    db = Database()
    d.load(db)
    return d, db


@pytest.mark.parametrize("schema,mod,make,scale", CASES)
def test_same_tables_columns_and_dtypes(schema, mod, make, scale):
    _, mine = _load(schema, scale)
    prog = make(seed=5, scale=scale)
    assert list(mine.tables) == list(prog.tables)
    assert mine.text_cols == prog.text_cols
    for t in prog.tables:
        assert list(mine.payloads[t][0]) == list(prog.payloads[t][0])
        assert len(mine.payloads[t]) == len(prog.payloads[t])
        mc, pc = mine.tables[t].columns, prog.tables[t].columns
        assert list(mc) == list(pc)
        for c in pc:
            assert mc[c].dtype == pc[c].dtype, (t, c)
        for k, v in prog.payloads[t][0].items():
            assert type(mine.payloads[t][0][k]) is type(v), (t, k)


@pytest.mark.parametrize("schema,mod,make,scale", CASES)
def test_same_key_ranges(schema, mod, make, scale):
    _, mine = _load(schema, scale)
    prog = make(seed=5, scale=scale)
    for t in prog.tables:
        for c, pv in prog.tables[t].columns.items():
            a, b = np.asarray(mine.tables[t].columns[c]), np.asarray(pv)
            if a.dtype.kind != "i":
                # floats: same range to within 2% of its width
                width = float(b.max() - b.min()) or 1.0
                assert abs(float(a.min() - b.min())) <= 0.02 * width
                assert abs(float(a.max() - b.max())) <= 0.02 * width
                continue
            width = int(b.max() - b.min()) or 1
            assert abs(int(a.min()) - int(b.min())) <= max(1, width // 50)
            assert abs(int(a.max()) - int(b.max())) <= max(1, width // 50)


@pytest.mark.parametrize("schema,mod,make,scale", CASES)
def test_truth_rates_within_sampling_error(schema, mod, make, scale):
    d, mine = _load(schema, scale)
    prog = make(seed=5, scale=scale)
    for name, phi in mod.PROMPTS.items():
        tables, _ = mod.LATENT[name]
        if len(tables) > 1:
            continue  # a join predicate: its rate is over pairs
        t = tables[0]
        fn = prog.truths[phi]
        p_rate = np.mean([bool(fn({t: r})) for r in prog.payloads[t]])
        m_rate = np.mean([bool(mine.truths[phi]({t: r}))
                          for r in mine.payloads[t]])
        n1, n2 = len(prog.payloads[t]), len(mine.payloads[t])
        p = (p_rate * n1 + m_rate * n2) / (n1 + n2)
        sigma = math.sqrt(max(p * (1 - p), 1e-6) * (1 / n1 + 1 / n2))
        assert abs(p_rate - m_rate) <= 5 * sigma, (name, p_rate, m_rate)


@pytest.mark.parametrize("schema,mod", [("ecommerce", ecommerce)])
def test_prompt_templates_are_the_programs(schema, mod):
    for name, phi in mod.PROMPTS.items():
        assert getattr(S, name) == phi


def test_same_seed_same_data_any_seed():
    a = datagen.generate("ecommerce", 2**40 + 3, 1.0)
    b = datagen.generate("ecommerce", 2**40 + 3, 1.0)
    c = datagen.generate("ecommerce", -5, 1.0)
    for t in a.tables:
        for col in a.tables[t]:
            assert list(a.tables[t][col]) == list(b.tables[t][col])
    assert list(a.tables["previews"]["text"]) != list(
        c.tables["previews"]["text"])


def test_permuted_keeps_every_row_and_moves_them():
    d = datagen.generate("ecommerce", 11, 1.0)
    a, b = d.permuted(2**31 + 1), d.permuted(2**31 + 2)
    assert d.permuted(2**31 + 1).records("previews") == a.records("previews")
    for t in d.tables:
        rows = sorted(map(repr, d.records(t)))
        assert sorted(map(repr, a.records(t))) == rows
        assert sorted(map(repr, b.records(t))) == rows
    assert a.records("products") != b.records("products")
