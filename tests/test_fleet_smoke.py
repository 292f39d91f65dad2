"""The autoscaling loop closed over the REAL ``FleetScaler`` and
``select_route`` in virtual time (``fleet_sim.py``: seconds of wall
clock for ten simulated minutes). Every assertion is a count or an
identity; the simulator gives no rate."""

import pytest

import fleet_sim


@pytest.mark.level("minimal")
def test_scaler_tracks_ramp_across_controller_kill(tmp_path):
    """A seeded diurnal ramp from zero replicas and back, with a
    controller kill on the plateau: the scaler follows the load up and
    down, every cold start lands inside the budget, nothing flaps, and
    the kill leaves no trace in the durable decision log."""
    arrivals = fleet_sim.diurnal_arrivals()
    control = fleet_sim.run_tracking(arrivals, str(tmp_path), kill=False)
    killed = fleet_sim.run_tracking(arrivals, str(tmp_path), kill=True)

    rows = killed["decisions"]
    ups = sum(1 for _, frm, to, _kind in rows if to > frm)
    downs = sum(1 for _, frm, to, _kind in rows if to < frm)
    assert ups >= 2 and downs >= 1, rows
    assert killed["peak_replicas"] >= 4
    # pod-lag chaos included, every pod is ready inside the budget
    assert len(killed["cold_starts"]) >= 3
    assert killed["lagged_pods"] >= 1
    assert max(killed["cold_starts"]) <= fleet_sim.COLD_START_BUDGET_S
    assert killed["flaps"] == 0 and control["flaps"] == 0
    # the kill hit mid-trace, and a faithful resume re-decides nothing:
    # the killed run's log equals the control's, row for row
    assert killed["decisions_at_kill"] > 0
    assert rows == control["decisions"]
    # scale-from-zero parks programs instead of failing them, and the
    # idle tail crosses the scale-to-zero grace back to zero replicas
    assert killed["parked"] > 0
    assert killed["scaled_to_zero"] and control["scaled_to_zero"]


@pytest.mark.level("minimal")
def test_eta_routing_beats_round_robin_on_first_token_count():
    """On a fleet of two fast pods and two at half speed, under the
    same seeded arrivals, more programs get their first token inside
    the limit when ``select_route`` places them than when they are
    dealt round-robin."""
    routed = fleet_sim.run_routing(routed=True)
    dealt = fleet_sim.run_routing(routed=False)
    assert dealt < routed <= fleet_sim.ROUTED_PROGRAMS, (routed, dealt)
