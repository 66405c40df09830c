"""LRC(12,2,2) through the normal entry points: the repair planner (a
local group where one is healthy, k survivors otherwise), decode of every
decodable loss pattern, and the health plane's guaranteed margin.  The
matrix and fragments against the plain reference are in
tests/rsbench/test_rsbench_lrc.py."""

import itertools
import os

import numpy as np
import pytest

from gpu_rscode_tpu import api, cli
from gpu_rscode_tpu.codec import RSCodec
from gpu_rscode_tpu.models import lrc
from gpu_rscode_tpu.models.vandermonde import total_matrix
from gpu_rscode_tpu.obs import health, metrics
from gpu_rscode_tpu.ops.gf import get_field
from gpu_rscode_tpu.utils.fileformat import chunk_file_name, write_conf

GEN = "lrc-12-2-2"
K, N = 12, 16
SIZE = K * 4096 + 777  # the last fragment tail-padded
GROUP = {0: list(range(6)) + [12], 1: list(range(6, 12)) + [13]}


def _group(i):
    return GROUP[0] if i in GROUP[0] else GROUP[1]


@pytest.fixture
def registry():
    metrics.REGISTRY.reset()
    metrics.force_enable()
    yield metrics.REGISTRY
    metrics.force_enable(False)
    metrics.REGISTRY.reset()


@pytest.fixture
def archive(tmp_path):
    data = np.random.default_rng(27).integers(
        0, 256, SIZE, dtype=np.uint8).tobytes()
    path = str(tmp_path / "extent.bin")
    with open(path, "wb") as fp:
        fp.write(data)
    api.encode_file(path, K, N - K, generator=GEN, checksums=True)
    frags = [open(chunk_file_name(path, i), "rb").read() for i in range(N)]
    return path, data, frags


def _count(name, labels=""):
    return metrics.REGISTRY.snapshot().get(name, {}).get(
        "values", {}).get(labels, 0)


def _reads(monkeypatch):
    """Chunk indices each rebuild segment read, through the fault
    plane's read hook."""
    seen = []
    real = api._faults.on_reads

    def spy(paths, indices):
        seen.append(tuple(indices))
        return real(paths, indices)

    monkeypatch.setattr(api._faults, "on_reads", spy)
    return seen


# -- the repair planner -------------------------------------------------------


@pytest.mark.parametrize("lost", range(N))
def test_repair_reads_the_local_group_or_k(archive, registry, monkeypatch,
                                           lost):
    """Every fragment is rebuilt byte for byte; a data or local-parity
    fragment from the other six of its group, a global parity from the
    twelve data fragments."""
    path, _, frags = archive
    os.remove(chunk_file_name(path, lost))
    seen = _reads(monkeypatch)
    assert api.repair_file(path) == [lost]
    assert open(chunk_file_name(path, lost), "rb").read() == frags[lost]
    local = lost < K + 2
    want = sorted(set(_group(lost)) - {lost}) if local else list(range(K))
    assert seen and all(list(s) == want for s in seen)
    assert _count("rs_repair_survivors_read_total") == len(want)
    plan = '{plan="local"}' if local else '{plan="global"}'
    assert _count("rs_repair_plans_total", plan) == 1
    assert api.scan_file(path)["healthy"] == list(range(N))  # CRC refreshed


@pytest.mark.parametrize("lost", [(0, 1), (12, 3), (0, 7), (4, 15)])
def test_losses_a_group_cannot_cover_take_the_global_plan(
        archive, registry, monkeypatch, lost):
    """Two losses in one group, or local plans that together read k."""
    path, _, frags = archive
    for i in lost:
        os.remove(chunk_file_name(path, i))
    seen = _reads(monkeypatch)
    assert api.repair_file(path) == sorted(lost)
    for i in lost:
        assert open(chunk_file_name(path, i), "rb").read() == frags[i]
    assert all(len(s) == K for s in seen)
    assert _count("rs_repair_plans_total", '{plan="global"}') == 2


def test_fleet_repair_takes_the_local_plan(archive, registry, monkeypatch):
    path, _, frags = archive
    os.remove(chunk_file_name(path, 8))
    seen = _reads(monkeypatch)
    assert api.repair_fleet([path]) == {path: [8]}
    assert open(chunk_file_name(path, 8), "rb").read() == frags[8]
    assert all(list(s) == [6, 7, 9, 10, 11, 13] for s in seen)


@pytest.mark.parametrize("lost", [(0,), (11,), (3, 12), (0, 1, 2, 3)])
def test_rs_planner_is_the_subset_selection(tmp_path, lost):
    """For an MDS archive the planner picks what _select_decodable_subset
    picks, with the rebuild rows T[targets] . inv it always used."""
    path = str(tmp_path / "f.bin")
    with open(path, "wb") as fp:
        fp.write(np.random.default_rng(5).integers(
            0, 256, 10 * 999, dtype=np.uint8).tobytes())
    api.encode_file(path, 10, 4, checksums=True)
    for i in lost:
        os.remove(chunk_file_name(path, i))
    scan = api._scan_chunks(path, api.DEFAULT_SEGMENT_BYTES)
    api.clear_subset_cache()
    chosen, inv = api._select_decodable_subset(scan)
    api.clear_subset_cache()
    got, mat = api._plan_repair(scan)
    gf = get_field(8)
    assert got == chosen and len(got) == 10
    np.testing.assert_array_equal(
        mat, gf.matmul(scan.total_mat[scan.unhealthy], inv))
    assert lrc.local_plans(total_matrix(4, 10), 10) == {}


# -- decode -------------------------------------------------------------------


def _decodable(scan):
    try:
        api._select_decodable_subset(scan)
        return True
    except ValueError:
        return False


@pytest.mark.parametrize("losses,decodable", [(3, 560), (4, 1568)])
def test_decodable_loss_patterns(archive, losses, decodable):
    """All 560 three-loss patterns decode, and 1,568 of the 1,820
    four-loss ones (86.2%, the paper's figure): the count decode's own
    subset search gives, over the matrix .METADATA holds."""
    path, _, _ = archive
    full = api._scan_chunks(path, api.DEFAULT_SEGMENT_BYTES)
    assert lrc.erasure_tolerance(full.total_mat, K) == 3
    n = 0
    for lost in itertools.combinations(range(N), losses):
        api.clear_subset_cache()
        n += _decodable(full.excluding(
            {i: chunk_file_name(path, i) for i in lost}))
    assert n == decodable


@pytest.mark.parametrize("seed", range(4))
def test_decode_file_restores_three_losses(archive, tmp_path, seed):
    path, data, _ = archive
    lost = sorted(np.random.default_rng(seed).choice(N, 3, replace=False))
    for i in lost:
        os.remove(chunk_file_name(path, i))
    out = api.auto_decode_file(path, str(tmp_path / "auto.out"))
    assert open(out, "rb").read() == data
    assert api.scan_file(path)["decodable"] is True
    scan = api._scan_chunks(path, api.DEFAULT_SEGMENT_BYTES)
    chosen, _ = api._select_decodable_subset(scan)
    conf = str(tmp_path / "conf")
    write_conf(conf, [os.path.basename(chunk_file_name(path, i))
                      for i in chosen])
    out = api.decode_file(path, conf, str(tmp_path / "conf.out"))
    assert open(out, "rb").read() == data


def test_an_undecodable_four_loss_pattern_raises(archive, tmp_path):
    """x0, x1, x2 and Lx lost: three unknowns against the two globals."""
    path, _, _ = archive
    for i in (0, 1, 2, 12):
        os.remove(chunk_file_name(path, i))
    assert api.scan_file(path)["decodable"] is False
    with pytest.raises(ValueError):
        api.auto_decode_file(path, str(tmp_path / "out"))
    with pytest.raises(ValueError):
        api.repair_file(path)


def test_cli_encodes_with_the_lrc_generator(tmp_path):
    path = str(tmp_path / "c.bin")
    with open(path, "wb") as fp:
        fp.write(os.urandom(5000))
    assert cli.main(["-k", "12", "-n", "16", "-e", path,
                     "--generator", GEN, "--quiet"]) == 0
    scan = api._scan_chunks(path, api.DEFAULT_SEGMENT_BYTES)
    np.testing.assert_array_equal(
        scan.total_mat,
        RSCodec(K, N - K, generator=GEN, strategy="bitplane").total_matrix)
    with pytest.raises(ValueError, match="k=12, p=4"):
        RSCodec(10, 4, generator=GEN, strategy="bitplane")


# -- health -------------------------------------------------------------------


def test_health_margin_is_the_guaranteed_tolerance(archive, tmp_path,
                                                   monkeypatch):
    """One lost fragment leaves an LRC(12,2,2) archive a margin of 2 (any
    3 losses decode, not any 4), where an RS(10,4) one keeps 3."""
    ledger = str(tmp_path / "runlog.jsonl")
    monkeypatch.setenv("RS_RUNLOG", ledger)
    monkeypatch.delenv("RS_RUNLOG_MAX_BYTES", raising=False)
    path, _, _ = archive
    rs = str(tmp_path / "rs.bin")
    with open(rs, "wb") as fp:
        fp.write(os.urandom(9999))
    api.encode_file(rs, 10, 4, checksums=True)
    for p in (path, rs):
        os.remove(chunk_file_name(p, 0))
        api.scan_file(p)
    st = health.load(ledger)["archives"]
    lrc_row = health.risk(st[os.path.abspath(path)])
    rs_row = health.risk(st[os.path.abspath(rs)])
    assert st[os.path.abspath(path)]["tolerance"] == 3
    assert "tolerance" not in st[os.path.abspath(rs)]
    assert (lrc_row["margin"], rs_row["margin"]) == (2, 3)
    assert lrc_row["terms"]["margin"] == 0.25  # 1 / (3 + 1)
