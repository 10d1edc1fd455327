"""PARSEC 3.0 canneal (Bienia et al., "The PARSEC Benchmark Suite",
PACT 2008) as an analytic trace generator.

canneal places a chip netlist by simulated annealing.  Each thread runs
``annealer_thread::Run``: per temperature step ``T /= 1.5``, then
``swaps_per_temp / nthreads`` moves of

* ``a = b; b = get_random_element(&b_id, a_id, rng)`` (pointer
  arithmetic only: no memory reference);
* ``calculate_delta_routing_cost(a, b)``: read ``a->present_loc`` and
  ``b->present_loc``, then ``a->swap_cost(a_loc, b_loc)`` plus
  ``b->swap_cost(b_loc, a_loc)``;
* ``netlist_elem::swap_cost(old, new)``: ``fanin.size()`` and
  ``fanout.size()`` (each vector's begin and end pointers), the old and
  new locations' ``x`` and ``y`` (loop invariant, loaded once), then
  for each ``fanin[i]`` and ``fanout[i]``: the fan buffer's pointer,
  the neighbour's ``present_loc``, that location's ``x`` and ``y``;
  the cost is ``yes_swap - no_swap`` over Manhattan distances;
* ``accept_move``: accept if ``delta < 0``; reject if ``delta == 0``;
  otherwise accept if ``exp(-delta / T) > drand()``;
* ``netlist::swap_locations(a, b)`` when accepted: both ``present_loc``
  pointers read, then both written.

The trace transcribes one serial move stream.  Each temperature step's
moves are the instances of one basic block (``cnl.move.t<step>``), so
Algorithm 1 splits every step's moves evenly across threads, as
canneal's ``_moves_per_thread_temp = swaps_per_temp / nthreads`` does.
Every reference is to the shared netlist, its locations or its fan
buffers: all are marked shared.

Layout (x86-64 libstdc++): a ``netlist_elem`` is 88 B (``std::string``
32 B, two ``std::vector`` headers of 24 B, an 8-B ``present_loc``); a
``location_t`` is two ``int`` (8 B); fan buffers hold 8-B pointers.
Arrays are laid out page-aligned in this order: the chip's elements,
its locations (row-major ``_locations[x][y]``), every element's fan-in
buffer back to back, then every fan-out buffer.

The netlist is synthetic and seeded: canneal's ``400000.nets`` is not
in the repository.  Fan-in counts are geometric (mean 1.5, at least 1),
fan-in ids uniform over the other elements, fan-outs the reverse edges
in file order (as ``netlist`` builds them while reading); the chip is
the smallest square grid larger than the element count, element ``i``
first placed at location ``i``.  numpy's seeded generator stands in for
canneal's MTRand: stream ``[seed, 0]`` draws the netlist, stream
``[seed, 1]`` the moves.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from repro.core.runtime_model import OpCounts
from repro.workloads.polybench import Workload, _counts
from repro.workloads.tracegen import AddressSpace, TraceBuilder

ELEM_BYTES = 88
FANIN_OFF, FANOUT_OFF, LOC_OFF = 32, 56, 80
PTR = 8
LOC_BYTES = 8
MEAN_FANIN = 1.5
COOLING = 1.5
# libm's exp is counted as this many floating-point operations
EXP_FP_OPS = 20


@dataclasses.dataclass(frozen=True)
class Netlist:
    """Element ``i``'s fan-ins are ``fanin[fanin_start[i]:fanin_start[i
    + 1]]``, its fan-outs likewise; locations form a ``side x side``
    grid, location ``l`` at ``x = l // side``, ``y = l % side``."""

    num_elements: int
    side: int
    fanin_start: np.ndarray
    fanin: np.ndarray
    fanout_start: np.ndarray
    fanout: np.ndarray

    @property
    def chip_size(self) -> int:
        return self.side * self.side


def make_netlist(num_elements: int, seed: int) -> Netlist:
    """The seeded synthetic netlist (stream ``[seed, 0]``)."""
    if num_elements < 2:
        raise ValueError("canneal needs at least two elements")
    rng = np.random.default_rng([seed, 0])
    k = rng.geometric(1.0 / MEAN_FANIN, num_elements)
    src = np.repeat(np.arange(num_elements, dtype=np.int64), k)
    dst = rng.integers(0, num_elements - 1, len(src))
    dst = dst + (dst >= src)                       # never the element itself
    order = np.argsort(dst, kind="stable")         # reverse edges, file order
    return Netlist(
        num_elements=num_elements,
        side=math.isqrt(num_elements) + 1,
        fanin_start=np.concatenate([[0], np.cumsum(k)]),
        fanin=dst,
        fanout_start=np.concatenate(
            [[0], np.cumsum(np.bincount(dst, minlength=num_elements))]),
        fanout=src[order],
    )


@dataclasses.dataclass(frozen=True)
class Layout:
    """Base addresses of the four arrays (``AddressSpace``, page aligned)."""

    elements: int
    locations: int
    fanin: int
    fanout: int
    total_bytes: int

    @classmethod
    def of(cls, net: Netlist) -> "Layout":
        sp = AddressSpace()
        arrays = [sp.array("elements", net.chip_size, ELEM_BYTES // 8),
                  sp.array("locations", net.chip_size, LOC_BYTES // 8),
                  sp.array("fanin", len(net.fanin)),
                  sp.array("fanout", len(net.fanout))]
        return cls(*(a.base for a in arrays), sp.total_bytes)


@dataclasses.dataclass
class Annealing:
    """One annealing run: its trace blocks, the operations it counted,
    each element's final location, and the accepted moves."""

    builder: TraceBuilder
    counts: OpCounts
    placement: np.ndarray
    accepted_good: int
    accepted_bad: int


def anneal(net: Netlist, *, swaps_per_temp: int, start_temp: float,
           temp_steps: int, seed: int) -> Annealing:
    """Run the move loop serially (stream ``[seed, 1]``), recording each
    move's references as one instance of its step's block."""
    rng = np.random.default_rng([seed, 1])
    lay = Layout.of(net)
    n, side = net.num_elements, net.side
    el, lo = lay.elements, lay.locations
    fans = ((lay.fanin, net.fanin_start.tolist(), net.fanin.tolist()),
            (lay.fanout, net.fanout_start.tolist(), net.fanout.tolist()))
    loc = list(range(n))
    ops = {"int": 0, "fp": 0, "div": 0, "loads": 0, "stores": 0}

    def random_element(different_from: int) -> int:
        while True:
            i = int(rng.integers(n))
            if i != different_from:
                return i

    def swap_cost(e: int, old: int, new: int, refs: list) -> int:
        base = el + e * ELEM_BYTES
        refs += (base + FANIN_OFF, base + FANIN_OFF + PTR,
                 base + FANOUT_OFF, base + FANOUT_OFF + PTR,
                 lo + old * LOC_BYTES, lo + old * LOC_BYTES + 4,
                 lo + new * LOC_BYTES, lo + new * LOC_BYTES + 4)
        ox, oy = divmod(old, side)
        nx, ny = divmod(new, side)
        no_swap = yes_swap = 0
        fan = 0
        for pool, start, ids in fans:
            for p in range(start[e], start[e + 1]):
                j = ids[p]
                lj = loc[j]
                refs += (pool + p * PTR, el + j * ELEM_BYTES + LOC_OFF,
                         lo + lj * LOC_BYTES, lo + lj * LOC_BYTES + 4)
                jx, jy = divmod(lj, side)
                no_swap += abs(ox - jx) + abs(oy - jy)
                yes_swap += abs(nx - jx) + abs(ny - jy)
                fan += 1
        # per neighbour: 4 subtractions, the loop's increment and compare;
        # 4 fabs and 4 adds; then yes_swap - no_swap
        ops["int"] += 6 * fan
        ops["fp"] += 8 * fan + 1
        return yes_swap - no_swap

    tb = TraceBuilder()
    good = bad = 0
    random_element(-1)                      # a, replaced before first use
    b = random_element(-1)
    temp = float(start_temp)
    for step in range(temp_steps):
        temp = temp / COOLING
        name = f"cnl.move.t{step}"
        for _ in range(swaps_per_temp):
            a = b
            b = random_element(a)
            pa = el + a * ELEM_BYTES + LOC_OFF
            pb = el + b * ELEM_BYTES + LOC_OFF
            refs = [pa, pb]
            la, lb = loc[a], loc[b]
            delta = swap_cost(a, la, lb, refs) + swap_cost(b, lb, la, refs)
            ops["fp"] += 2                  # delta's add, delta < 0
            if delta < 0:
                accept, good = True, good + 1
            elif delta == 0:
                accept = False
                ops["fp"] += 1
            else:
                accept = math.exp(-delta / temp) > rng.random()
                bad += accept
                ops["fp"] += 2 + EXP_FP_OPS  # == 0, > drand, exp
                ops["div"] += 1
            ops["loads"] += len(refs)
            if accept:
                loc[a], loc[b] = lb, la
                refs += (pa, pb, pa, pb)
                ops["loads"] += 2
                ops["stores"] += 2
            tb.instance(name, [(refs, True)])
    counts = _counts(fp=float(ops["fp"]), ints=float(ops["int"]),
                     divs=float(ops["div"]), loads=float(ops["loads"]),
                     stores=float(ops["stores"]))
    return Annealing(tb, counts, np.asarray(loc, dtype=np.int64), good, bad)


def make_canneal(num_elements: int = 4096, swaps_per_temp: int = 1024,
                 start_temp: float = 2000.0, temp_steps: int = 4,
                 seed: int = 0) -> Workload:
    """canneal ``<threads> <swaps_per_temp> <start_temp> <netlist>
    <temp_steps>`` on a seeded netlist of ``num_elements``.  The move
    stream decides the operation counts, so it runs here, once."""
    net = make_netlist(num_elements, seed)
    run = anneal(net, swaps_per_temp=swaps_per_temp, start_temp=start_temp,
                 temp_steps=temp_steps, seed=seed)
    return Workload("Canneal", "cnl", "PARSEC", run.builder.build,
                    run.counts)


# simlarge: ``canneal <threads> 15000 2000 400000.nets 128``, cut to 4
# temperature steps (60,000 moves)
SIZE_PRESETS: dict[str, dict] = {
    "simlarge-t4": dict(num_elements=400_000, swaps_per_temp=15_000,
                        start_temp=2000.0, temp_steps=4, seed=0),
    "smoke": dict(num_elements=320, swaps_per_temp=240, start_temp=2000.0,
                  temp_steps=2, seed=0),
}


def resolved_kwargs(sizes: str | None) -> dict:
    """The maker's kwargs at a preset (None: the maker's defaults)."""
    import inspect

    defaults = {k: p.default for k, p in
                inspect.signature(make_canneal).parameters.items()}
    return {**defaults, **(SIZE_PRESETS[sizes] if sizes else {})}
