"""`correct` comes out false when the timed path is broken, and true when it
is not. Each case drives a whole run of a cell -- server, warm-up, traffic,
drain, comparison with the plain reference -- on the CPU at a size a test
run can hold, past the harness's look for a chip. The control serves with
a path of the program that breaks the guarantee the configuration
states; the faults are
planted under the server, in the filter dispatch every request goes
through."""
from __future__ import annotations

import numpy as np
import pytest

import harness
from reference import filter_ref

TINY = (24, 32)


def tiny(name: str, **traffic) -> harness.Cell:
    cell = harness.load_cell(name)
    cfg = dict(cell.config, images={k: list(TINY) for k in
                                    cell.config["images"]},
               distinct_images=4)
    if cfg["server"].get("pool"):
        # four members, all on the one CPU device
        cfg["server"] = dict(cfg["server"], pool=[[0], [0], [0], [0]])
    cell.config = cfg
    loop = cell.traffic["loop"]
    small = ({"in_flight": 8} if loop == "closed" else
             {"rate_per_s": 60} if loop == "open" else {"period_ms": 50.0})
    cell.traffic = {**cell.traffic, "check_sample": 32, **small, **traffic}
    return cell


def run(cell: harness.Cell, seed: int = 2**32 + 5, **kw) -> dict:
    return harness.run_cell(cell, seed, 0.5, **kw,
                            peak={"int8_ops_per_s": 393e12,
                                  "hbm_bytes_per_s": 819e9})


@pytest.mark.parametrize("name", ["fvc2004.enrol_backlog",
                                  "fvc2004.verify_open", "camera1080.stream",
                                  "fvc2004_pool4.verify_open",
                                  "fvc2004_mitchell.enrol_backlog"])
def test_sound_run_is_correct(name):
    line = run(tiny(name))
    assert line["correct"], line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["mismatched_px"]["value"] == 0
    assert list(line)[-1] == "checks"
    assert "setup_s" in line["metrics"]
    assert line["load"]["jaxpr_trace_duration_in_window"] == 0


@pytest.mark.parametrize("name", ["fvc2004.enrol_backlog",
                                  "camera1080.stream",
                                  "fvc2004_mitchell.enrol_backlog"])
def test_control_is_not_correct(name):
    """fvc2004's control is the Mitchell multiplier; camera1080's gaussian3
    taps are powers of two, on which Mitchell is exact, so its control is
    REFMLM at 4 bits (int4 for int8); fvc2004_mitchell's is exact REFMLM,
    which differs from Mitchell on gaussian5's tap 6."""
    line = run(tiny(name), control=True)
    assert not line["correct"]
    assert line["checks"]["mismatched_px"]["value"] > 0


def _planted(fault):
    """A stand-in for the filter dispatch that breaks its outputs."""
    from repro.filters.pipeline import apply_filter_batch

    def broken(imgs, filt, **kw):
        outs = apply_filter_batch(imgs, filt, **kw)
        return fault(imgs, outs)
    return broken


def _unchanged(imgs, outs):
    return [np.asarray(im, np.uint8) for im in imgs]


def _half_left_out(imgs, outs):
    keep = max(1, len(outs) // 2)
    return outs[:keep] + [np.zeros_like(o) for o in outs[keep:]]


def _altered(imgs, outs):
    bad = [o.copy() for o in outs]
    bad[-1][0, 0] ^= 1
    return bad


# half of a batch can be left out only where batches hold more than one
# request: the closed loop's full batches
@pytest.mark.parametrize("name,fault", [
    ("fvc2004.enrol_backlog", _unchanged),
    ("fvc2004.enrol_backlog", _half_left_out),
    ("fvc2004.enrol_backlog", _altered),
    ("camera1080.stream", _unchanged),
    ("camera1080.stream", _altered),
    ("fvc2004.verify_open", _altered),
    ("fvc2004_pool4.verify_open", _altered),
    ("fvc2004_mitchell.enrol_backlog", _unchanged),
    ("fvc2004_mitchell.enrol_backlog", _half_left_out),
    ("fvc2004_mitchell.enrol_backlog", _altered),
], ids=lambda v: v if isinstance(v, str) else v.__name__.strip("_"))
def test_planted_fault_is_not_correct(monkeypatch, name, fault):
    import repro.serve.workload as workload
    monkeypatch.setattr(workload, "apply_filter_batch", _planted(fault))
    line = run(tiny(name, check_sample=1024))
    assert not line["correct"]
    assert line["checks"]["mismatched_px"]["value"] > 0


def test_reference_matches_the_program_oracle():
    """The plain reference and the program's own jnp oracle agree on every
    filter every configuration states (and so REFMLM equals exact)."""
    import jax.numpy as jnp
    from repro.filters.ref import apply_filter_ref

    rng = np.random.default_rng(0)
    imgs = rng.integers(0, 256, (3, 19, 23), dtype=np.uint8)
    imgs[0] = 255
    specs = {}
    for name in ("fvc2004", "camera1080"):
        specs.update(harness.load_cell(
            next(w["name"] for w in harness.load_spec()["workloads"]
                 if w["config"] == name)).config["filters"])
    for filt, spec in specs.items():
        want = np.asarray(apply_filter_ref(jnp.asarray(imgs), filt,
                                           method="refmlm"))
        exact = np.asarray(apply_filter_ref(jnp.asarray(imgs), filt,
                                            method="exact"))
        got = np.stack([filter_ref(im, spec) for im in imgs])
        assert (got == want).all() and (got == exact).all(), filt


def test_mitchell_reference_matches_the_program_oracle():
    """The plain Mitchell reference (eq. 8, row pass then column pass) and
    the program's own jnp oracle agree on every filter fvc2004_mitchell
    states, all-0 and all-255 images among them; gaussian5's tap 6 makes
    it differ from exact filtering."""
    import jax.numpy as jnp
    from repro.filters.ref import apply_filter_ref

    rng = np.random.default_rng(1)
    imgs = rng.integers(0, 256, (4, 19, 23), dtype=np.uint8)
    imgs[0] = 0
    imgs[1] = 255
    cell = harness.load_cell("fvc2004_mitchell.enrol_backlog")
    assert cell.config["method"] == "mitchell"
    for filt, spec in cell.config["filters"].items():
        want = np.asarray(apply_filter_ref(jnp.asarray(imgs), filt,
                                           method="mitchell"))
        got = np.stack([filter_ref(im, spec, method="mitchell")
                        for im in imgs])
        assert got.dtype == np.uint8
        assert (got == want).all(), filt
    exact = filter_ref(imgs[2], cell.config["filters"]["gaussian5"])
    assert (filter_ref(imgs[2], cell.config["filters"]["gaussian5"],
                       method="mitchell") != exact).any()
