"""The canneal generator (``parsec/canneal``) against a plain per-move
transcription of canneal's ``annealer_thread::Run``, ``swap_cost``,
``accept_move`` and ``swap_locations`` over the stated layout."""
from __future__ import annotations

import math

import numpy as np
import pytest

from repro.core.trace.types import trace_from_blocks
from repro.workloads import parsec, registry

PAGE = 4096


class Location:
    def __init__(self, index: int, side: int):
        self.index = index
        self.x, self.y = divmod(index, side)


class Elem:
    def __init__(self, index: int):
        self.index = index
        self.fanin: list[Elem] = []
        self.fanout: list[Elem] = []
        self.present_loc: Location | None = None


def _align(n: int) -> int:
    return -(-n // PAGE) * PAGE


class Chip:
    """The netlist as canneal holds it, with each field's address on
    the x86-64 layout: 88-B elements (name 32 B, fanin and fanout
    vector headers 24 B each, present_loc 8 B), 8-B locations,
    8-B fan pointers, arrays page-aligned from 4 KiB in that order."""

    def __init__(self, net: parsec.Netlist):
        chip = net.side * net.side
        edges = len(net.fanin)
        self.elem_base = PAGE
        self.loc_base = self.elem_base + _align(88 * chip)
        self.fanin_base = self.loc_base + _align(8 * chip)
        self.fanout_base = self.fanin_base + _align(8 * edges)
        self.locations = [Location(i, net.side) for i in range(chip)]
        self.elements = [Elem(i) for i in range(chip)]
        for i in range(chip):
            self.elements[i].present_loc = self.locations[i]
        # the buffers' slots, in the order the file lists the edges
        self.fanin_slot: dict[tuple[int, int], int] = {}
        self.fanout_slot: dict[tuple[int, int], int] = {}
        slot = 0
        for i in range(net.num_elements):
            for j in net.fanin[net.fanin_start[i]:net.fanin_start[i + 1]]:
                self.fanin_slot[(i, len(self.elements[i].fanin))] = slot
                self.elements[i].fanin.append(self.elements[int(j)])
                slot += 1
        slot = 0
        for i in range(net.num_elements):
            for j in net.fanout[net.fanout_start[i]:net.fanout_start[i + 1]]:
                self.fanout_slot[(i, len(self.elements[i].fanout))] = slot
                self.elements[i].fanout.append(self.elements[int(j)])
                slot += 1

    def name(self, e: Elem) -> int:
        return self.elem_base + 88 * e.index

    def present_loc(self, e: Elem) -> int:
        return self.name(e) + 80

    def x(self, loc: Location) -> int:
        return self.loc_base + 8 * loc.index

    def y(self, loc: Location) -> int:
        return self.loc_base + 8 * loc.index + 4


def reference(net: parsec.Netlist, swaps_per_temp: int, start_temp: float,
              temp_steps: int, seed: int):
    chip = Chip(net)
    rng = np.random.default_rng([seed, 1])
    blocks = []
    good = bad = 0

    def get_random_element(different_from):
        elem_id = int(rng.integers(net.num_elements))
        while elem_id == different_from:
            elem_id = int(rng.integers(net.num_elements))
        return elem_id

    def swap_cost(elem, old_loc, new_loc, refs):
        refs.append(chip.name(elem) + 32)          # fanin.size()
        refs.append(chip.name(elem) + 40)
        refs.append(chip.name(elem) + 56)          # fanout.size()
        refs.append(chip.name(elem) + 64)
        refs += [chip.x(old_loc), chip.y(old_loc),
                 chip.x(new_loc), chip.y(new_loc)]
        no_swap = yes_swap = 0
        for i, fanin in enumerate(elem.fanin):
            refs.append(chip.fanin_base + 8 * chip.fanin_slot[(elem.index, i)])
            refs.append(chip.present_loc(fanin))
            fanin_loc = fanin.present_loc
            refs += [chip.x(fanin_loc), chip.y(fanin_loc)]
            no_swap += abs(old_loc.x - fanin_loc.x)
            no_swap += abs(old_loc.y - fanin_loc.y)
            yes_swap += abs(new_loc.x - fanin_loc.x)
            yes_swap += abs(new_loc.y - fanin_loc.y)
        for i, fanout in enumerate(elem.fanout):
            refs.append(
                chip.fanout_base + 8 * chip.fanout_slot[(elem.index, i)])
            refs.append(chip.present_loc(fanout))
            fanout_loc = fanout.present_loc
            refs += [chip.x(fanout_loc), chip.y(fanout_loc)]
            no_swap += abs(old_loc.x - fanout_loc.x)
            no_swap += abs(old_loc.y - fanout_loc.y)
            yes_swap += abs(new_loc.x - fanout_loc.x)
            yes_swap += abs(new_loc.y - fanout_loc.y)
        return yes_swap - no_swap

    def accept_move(delta_cost, temp):
        if delta_cost < 0:
            return "good"
        if delta_cost == 0:
            return "rejected"
        random_value = rng.random()
        boltzman = math.exp(-delta_cost / temp)
        return "bad" if boltzman > random_value else "rejected"

    get_random_element(-1)
    b_id = get_random_element(-1)
    temp = start_temp
    for step in range(temp_steps):
        temp = temp / 1.5
        for _ in range(swaps_per_temp):
            a_id = b_id
            b_id = get_random_element(a_id)
            a, b = chip.elements[a_id], chip.elements[b_id]
            refs = [chip.present_loc(a), chip.present_loc(b)]
            a_loc, b_loc = a.present_loc, b.present_loc
            delta = swap_cost(a, a_loc, b_loc, refs)
            delta += swap_cost(b, b_loc, a_loc, refs)
            decision = accept_move(delta, temp)
            if decision != "rejected":
                good += decision == "good"
                bad += decision == "bad"
                refs += [chip.present_loc(a), chip.present_loc(b)]
                a.present_loc, b.present_loc = b.present_loc, a.present_loc
                refs += [chip.present_loc(a), chip.present_loc(b)]
            blocks.append((f"cnl.move.t{step}", np.array(refs), True))
    placement = [chip.elements[i].present_loc.index
                 for i in range(net.num_elements)]
    return trace_from_blocks(blocks), placement, good, bad


@pytest.mark.parametrize("seed", [0, 7])
def test_generator_equals_the_plain_transcription(seed):
    net = parsec.make_netlist(64, seed)
    run = parsec.anneal(net, swaps_per_temp=50, start_temp=2000.0,
                        temp_steps=2, seed=seed)
    got = run.builder.build()
    want, placement, good, bad = reference(net, 50, 2000.0, 2, seed)
    assert got.addresses.tolist() == want.addresses.tolist()
    assert got.bb_ids.tolist() == want.bb_ids.tolist()
    assert got.shared_mask.tolist() == want.shared_mask.tolist()
    assert got.inst_ids.tolist() == want.inst_ids.tolist()
    assert run.placement.tolist() == placement
    assert (run.accepted_good, run.accepted_bad) == (good, bad)
    assert good > 0 and bad > 0 and good + bad < 100
    stores = 2 * (good + bad)
    assert run.counts.stores == stores
    assert run.counts.loads == len(got) - stores


def test_netlist_is_seeded_and_consistent():
    net = parsec.make_netlist(1000, 3)
    again = parsec.make_netlist(1000, 3)
    assert net.fanin.tolist() == again.fanin.tolist()
    assert net.fanin.tolist() != parsec.make_netlist(1000, 4).fanin.tolist()
    assert net.side ** 2 > 1000 > (net.side - 1) ** 2
    src = np.repeat(np.arange(1000), np.diff(net.fanin_start))
    assert np.all(net.fanin != src) and np.all(np.diff(net.fanin_start) >= 1)
    # fan-outs are the reverse edges, listed in file order
    edges = sorted(zip(net.fanin.tolist(), src.tolist()))
    outs = [(i, int(j)) for i in range(1000) for j in
            net.fanout[net.fanout_start[i]:net.fanout_start[i + 1]]]
    assert outs == edges


def _digest(source) -> str:
    import hashlib

    trace = source.trace()
    h = hashlib.sha256()
    for a in (trace.addresses, trace.bb_ids, trace.inst_ids,
              trace.shared_mask):
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def test_smoke_digest_is_stable_and_follows_the_seed():
    first = registry.resolve("parsec/canneal", "smoke")
    second = registry.resolve("parsec/canneal", "smoke")
    assert _digest(first) == _digest(second)
    other = parsec.make_canneal(**dict(parsec.SIZE_PRESETS["smoke"],
                                       seed=1))
    assert _digest(other) != _digest(first)
    assert first.op_counts == second.op_counts


def test_every_move_is_one_instance_split_per_step():
    """Algorithm 1 gives each core an even share of every step's moves,
    as canneal's ``_moves_per_thread_temp`` does."""
    from repro.core.trace.mimic import core_assignment

    kw = parsec.SIZE_PRESETS["smoke"]
    trace = registry.resolve("parsec/canneal", "smoke").trace()
    steps = kw["temp_steps"]
    assert trace.bb_counts == {s: kw["swaps_per_temp"] for s in range(steps)}
    assert trace.shared_mask.all()
    replicate, core = core_assignment(trace, 4)
    assert not replicate.any()
    firsts = trace._instance_firsts()
    for step in range(steps):
        mine = core[firsts][trace.bb_ids[firsts] == step]
        assert np.bincount(mine).tolist() == [kw["swaps_per_temp"] // 4] * 4
        assert np.all(np.diff(mine) >= 0)


def test_registry_resolves_canneal_and_a_sweep_scores_it():
    from repro.api import Session
    from repro.explore import FusedSweepEvaluator, SearchSpace

    assert "parsec/canneal" in registry.workload_names("parsec")
    assert registry.declared_fingerprint("parsec/canneal", "smoke") != \
        registry.declared_fingerprint("parsec/canneal", "simlarge-t4")
    source = registry.resolve("parsec/canneal", "smoke")
    space = SearchSpace(target="Xeon E5-2699 v4", level="L3",
                        sets=(64, 256), ways=(1, 20), cores=(1, 8))
    ev = FusedSweepEvaluator(source, space, session=Session(
        cache_model="batched"))
    res = ev.evaluate(space.configs())
    assert np.all((res.rates >= 0) & (res.rates <= 1))
    assert np.all(res.t_pred_s > 0)
