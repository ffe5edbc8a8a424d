"""The comparison that decides ``correct``, driven end to end at a size a
test can hold: the harness's look for a chip is skipped, everything else
of a run happens (set-up, warm-up, the served window, the reference).

A sound program comes out correct; with the timed path broken underneath
the same run comes out not correct, once for each fault a one-chip
serving cell can have: a verified count altered where the device backend
produces it, and half of a verification batch left out.  The control, the
reference computed in bfloat16 put in the program's place, comes out not
correct through the same comparison on every seed.  Each runs over the
float and the packed tier."""

import _bench_path
import pytest

from mbench import cell

# tiny masks answer far faster than the tier's per-tenant rate allows
TINY = {"n_masks": 192, "height": 48, "width": 48,
        "tier_settings": {"tenant_rate": 1e6, "tenant_burst": 1e6,
                          "queue_depth": 256, "batch_max": 32}}
SMALL_POOL = {"per_client": 32}
PATHS = cell.Paths(_bench_path.ROOT)
CELL = "packed448-gui-serial"
TIERS = ["float", "packed"]


def _run(tier, seed):
    return cell.run(PATHS, CELL, seed, 3.0, False, process_start=0.0,
                    require_tpu=False, cfg_override=dict(TINY, tier=tier),
                    mix_override=SMALL_POOL)


@pytest.mark.parametrize("tier", TIERS)
def test_sound_run_is_correct(tier):
    out = _run(tier, 2**33 + 5)
    assert out["correct"] is True
    assert out["checks"]["wrong_answers"]["value"] == 0
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out["checks"]) == ["wrong_answers", "unanswered"]
    assert list(out)[-1] == "checks"


def _altered(counts):
    counts[:, 0] += 1                      # one mask's count, every spec
    return counts


def _half_batch(counts):
    counts[:, counts.shape[1] // 2:] = 0   # half of the rows never verified
    return counts


@pytest.mark.parametrize("fault", [_altered, _half_batch],
                         ids=["altered_count", "half_batch_left_out"])
@pytest.mark.parametrize("tier", TIERS)
def test_broken_verification_makes_the_run_incorrect(monkeypatch, tier,
                                                     fault):
    from repro.core import backend

    real = backend.DeviceBackend.fused_counts

    def broken(self, store, positions, specs):
        return fault(real(self, store, positions, specs).copy())

    monkeypatch.setattr(backend.DeviceBackend, "fused_counts", broken)
    out = _run(tier, 2**33 + 6)
    assert out["correct"] is False
    assert out["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("tier", TIERS)
def test_control_fails_the_comparison(tier):
    rows = cell.control(PATHS, CELL, [3, 2**34 + 1, 77], 10.0,
                        require_tpu=False, cfg_override=dict(TINY, tier=tier),
                        mix_override=SMALL_POOL)
    assert all(r["correct"] is False and r["wrong_answers"] > 0
               for r in rows), rows


def test_control_records_pass_the_comparison_with_exact_answers():
    """The control's records carry what the served path carries: the
    float32 reference in the program's place comes out correct."""
    from mbench import deploy
    _, _, cfg, mix = cell.load_cell(PATHS, CELL, TINY, SMALL_POOL)
    params = deploy.params_for(cfg, 11)
    reqs = cell.window_requests(mix, 11, 3.0)
    i = next(i for i, r in enumerate(reqs) if r["spec"]["kind"] == "topk")
    reqs[i] = dict(reqs[i], session=True)       # a paged session too
    answers = cell.reference_answers(cfg, params, [r["spec"] for r in reqs])
    records = cell.control_records(reqs, answers, 25, 2)
    checked = cell.check(records, answers, 25)
    assert checked["wrong"] == 0 and checked["missing"] == 0
    assert checked["compared"] == len(reqs) + 2
