"""Hypothesis property tests for the campaign layer: any constructible
``CampaignSpec`` — arbitrary workload params, selector mixes, axis
grids, fault scenarios, seeds, budgets — round-trips through JSON
exactly (``from_json(to_json(s)) == s``), the serialization contract
the journal's spec echo and ``CampaignSpec.load`` depend on."""
import json

import pytest

pytest.importorskip("hypothesis")

import hypothesis.strategies as st
from hypothesis import assume, given, settings

from repro.campaign import Budget, CampaignSpec, PlatformSelector
from repro.faults import FaultSpec
from repro.workloads import WorkloadSpec

SETTINGS = settings(max_examples=60, deadline=None)

names = st.text(alphabet="abcdefghijklmnopqrstuvwxyz0123456789-_",
                min_size=1, max_size=16)
#: JSON-stable scalars (finite floats survive dumps/loads exactly)
scalars = st.one_of(st.integers(-2**31, 2**31),
                    st.floats(allow_nan=False, allow_infinity=False,
                              width=32),
                    names)


@st.composite
def workload_specs(draw):
    kind = draw(st.sampled_from(("hpl", "transformer")))
    params = draw(st.dictionaries(names, scalars, max_size=4))
    return WorkloadSpec(kind=kind, name=draw(names) if draw(st.booleans())
                        else "", params=tuple(sorted(params.items())))


@st.composite
def selectors(draw):
    if draw(st.booleans()):
        return PlatformSelector(registry=draw(names))
    return PlatformSelector(
        top500=draw(st.sampled_from(("sample:2020_06", "sample:2020_11",
                                     "/data/fleet.csv"))),
        edition=draw(names) if draw(st.booleans()) else "",
        limit=draw(st.integers(0, 500)))


@st.composite
def fault_specs(draw):
    if draw(st.booleans()):
        return None
    return FaultSpec.straggler(rank=draw(st.integers(0, 4095)),
                               slowdown=draw(st.floats(
                                   1.01, 32, allow_nan=False)),
                               seed=draw(st.integers(0, 2**31)))


@st.composite
def campaign_specs(draw):
    axes = draw(st.dictionaries(
        names, st.lists(scalars, min_size=1, max_size=4, unique=True),
        max_size=3))
    workloads = draw(st.lists(workload_specs(), max_size=3))
    platforms = draw(st.lists(selectors(), min_size=1, max_size=3))
    # registry selectors need a workload to run (CampaignSpec rejects it)
    assume(workloads or all(s.kind != "registry" for s in platforms))
    return CampaignSpec.make(
        draw(names),
        workloads=workloads,
        platforms=platforms,
        axes=axes,
        faults=draw(st.lists(fault_specs(), min_size=1, max_size=3)),
        seeds=draw(st.lists(st.integers(0, 2**31), min_size=1,
                            max_size=4, unique=True)),
        max_runs=draw(st.integers(1, 10**6)))


@SETTINGS
@given(campaign_specs())
def test_spec_round_trips_through_json(spec):
    assert CampaignSpec.from_json(spec.to_json()) == spec


@SETTINGS
@given(campaign_specs())
def test_spec_dict_form_is_json_safe_and_exact(spec):
    d = spec.to_dict()
    back = CampaignSpec.from_dict(json.loads(json.dumps(d)))
    assert back == spec and hash(back) == hash(spec)
    assert back.to_json() == spec.to_json()


@SETTINGS
@given(campaign_specs())
def test_spec_is_frozen_and_hashable(spec):
    with pytest.raises(Exception):
        spec.name = "other"
    assert isinstance(hash(spec), int)
    assert Budget(max_runs=spec.budget.max_runs) == spec.budget
