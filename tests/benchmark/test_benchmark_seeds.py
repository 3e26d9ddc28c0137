"""What --seed does, in the CPU rehearsal: the same seed gives the same
inputs, and another seed gives a differently labelled copy of the same
graph and the same traffic in another order, so nothing new compiles."""

import pytest

from rehearsal import over_cells, rehearse


@pytest.mark.parametrize("view,cell", over_cells())
def test_another_seed_same_work_and_nothing_new_to_compile(view, cell,
                                                            tmp_path,
                                                            monkeypatch):
    """A seed relabels the graph and reorders the traffic: the roots are
    the same vertices of the structure, the walls come from the same tiers,
    and, with every executable kept in the persistent cache, a run with a
    seed never seen before misses the cache not once."""
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    _, first, lines = rehearse(cell, tmp_path, view=view)
    _, other, other_lines = rehearse(cell, tmp_path, seed=12345, view=view)
    assert first["counts"]["setup_cache_misses"] > 0
    assert other["counts"]["setup_cache_misses"] == 0
    # what a driver prints of its inputs is that driver's to say: both of
    # today's print the digest of the edge list they made from the seed,
    # `submit-loop` also each warm-up submit's frontier tiers
    driver = view.traffic_of(cell)["driver"]
    if driver in ("submit-loop", "closed-loop-http"):
        assert _digests(lines) and _digests(lines) != _digests(other_lines)
    if driver == "submit-loop":
        assert _tiers(lines) and _tiers(lines) == _tiers(other_lines)


def _digests(lines):
    return [ln.split("digest ")[1].split()[0] for ln in lines
            if "digest " in ln]


def _tiers(lines):
    return sorted(ln.split("tiers=")[1] for ln in lines
                  if "warm-up submit" in ln)


def test_same_seed_same_inputs(tmp_path):
    _, _, first = rehearse("g500-olap.bfs", tmp_path)
    _, _, again = rehearse("g500-olap.bfs", tmp_path)
    assert _digests(first) and _digests(first) == _digests(again)
    roots = [ln.split("root=")[1].split(":")[0] for ln in first
             if "warm-up submit" in ln]
    assert len(set(roots)) == 16 and roots == [
        ln.split("root=")[1].split(":")[0] for ln in again
        if "warm-up submit" in ln]
