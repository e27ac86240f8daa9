"""The harness's FIFO row attribution against a sync-mode engine.

An async engine (the benchmark's mode) is fed uneven submissions, so
some users build a backlog and others idle; the ``Ledger`` mirrors every
tick and must count what ``step()`` returned.  A second, sync-mode
engine is then fed, tick by tick, exactly the rows the ledger says each
tick absorbed: if the attribution is right the two fleets are
bit-identical."""

import json
from pathlib import Path

import numpy as np
import pytest

import harness
from ledger import Ledger

CFG = json.loads((Path(harness.HERE) / "configs" / "telemetry-d64.json")
                 .read_text())


@pytest.mark.parametrize("seed", [3, 2**33 + 5])
def test_ledger_matches_a_sync_engine(seed):
    from repro.serve.engine import SketchFleetEngine

    S, block, d = 8, 8, 64
    cfg = harness.merge(CFG, {"system": {"streams": S},
                              "rows": {"pool": 4096}})
    rows = harness.load_module(Path(harness.HERE) / "configs"
                               / "telemetry-d64.py").Rows(cfg, seed)
    kw = dict(d=d, streams=S, eps=1 / 8, window=1024, block=block)
    a = SketchFleetEngine("dsfd", ingest="async", **kw)
    led = Ledger(S, block)
    g = np.random.default_rng(seed)
    for _ in range(40):
        counts = g.integers(0, 13, S) * (g.random(S) < 0.6)
        users = np.repeat(np.arange(S, dtype=np.int32), counts)
        g.shuffle(users)
        if users.size:
            ords = led.ordinals(users)
            assert a.submit_many(users, rows.make(users, ords)).all()
            led.admit(users)
        t_before = a.t
        n = a.step()
        assert led.tick(t_before, n) == n
        assert a.backlog == int(led.pending.sum())
    assert led.mismatches == 0
    assert a.rows_ingested == int(np.stack(led.takes).astype(int).sum())

    b = SketchFleetEngine("dsfd", ingest="sync", **kw)
    done = np.zeros(S, np.int64)
    for take in led.takes:
        take = take.astype(np.int64)
        users = np.repeat(np.arange(S, dtype=np.int32), take)
        ords = np.concatenate([np.arange(done[u], done[u] + take[u])
                               for u in range(S)])
        done += take
        if users.size:
            assert b.submit_many(users, rows.make(users, ords)).all()
        assert b.step() == int(take.sum())
    assert a.t == b.t
    for u in range(S):
        assert np.array_equal(a.query_user(u), b.query_user(u))
        ords, ts = led.stream(u, a.t)
        assert ords.size == done[u]
        assert np.all(np.diff(ts) > 0)
