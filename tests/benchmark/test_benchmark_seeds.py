"""What --seed does, in the CPU rehearsal: the same seed gives the same
inputs, and another seed gives a differently labelled copy of the same
graph and the same traffic in another order, so nothing new compiles."""

import pytest

from rehearsal import MANIFEST, rehearse


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_another_seed_same_work_and_nothing_new_to_compile(cell, tmp_path,
                                                            monkeypatch):
    """A seed relabels the graph and reorders the traffic: the roots are
    the same vertices of the structure, the walls come from the same tiers,
    and, with every executable kept in the persistent cache, a run with a
    seed never seen before misses the cache not once."""
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    _, first, lines = rehearse(cell, tmp_path)
    _, other, other_lines = rehearse(cell, tmp_path, seed=12345)
    assert first["counts"]["setup_cache_misses"] > 0
    assert other["counts"]["setup_cache_misses"] == 0

    def tiers(ls):
        return sorted(ln.split("tiers=")[1] for ln in ls
                      if "warm-up submit" in ln)

    assert tiers(lines) == tiers(other_lines)
    assert [ln for ln in lines if "digest" in ln] != [
        ln for ln in other_lines if "digest" in ln]


def test_same_seed_same_inputs(tmp_path):
    _, _, first = rehearse("g500-olap.bfs", tmp_path)
    _, _, again = rehearse("g500-olap.bfs", tmp_path)
    digest = [ln.split("digest ")[1].split()[0] for ln in first
              if "digest " in ln]
    assert digest and digest == [
        ln.split("digest ")[1].split()[0] for ln in again if "digest " in ln]
    roots = [ln.split("root=")[1].split(":")[0] for ln in first
             if "warm-up submit" in ln]
    assert len(set(roots)) == 16 and roots == [
        ln.split("root=")[1].split(":")[0] for ln in again
        if "warm-up submit" in ln]
