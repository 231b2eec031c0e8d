"""chip_smoke.py rehearsed on the CPU: the phase functions at a few hundred
lines, and the contract's refusals (no accelerator -> non-zero, no result
line).  What only the chip can show — Mosaic-compiled kernels, four real
devices — is chip_smoke.py's own job through the chip tool."""

import functools
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from helpers import py_wordcount

REPO = Path(__file__).resolve().parent.parent
# CPU-sized shapes (the chip run uses the CLI's defaults): a small block,
# and caps the sample corpus still fits exactly (<= 10 tokens a line,
# longest token 7 bytes).
SMALL = ("--block-lines", "256", "--key-width", "8", "--emits-per-line", "10")
# --stream under the CPU default (hasht) is rewritten onto the interpreted
# megakernel — minutes of CPU compile; the chip's default mode is hashp2.
TPU_MODE = ("--sort-mode", "hashp2")


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("smoke") / "corpus.txt")
    n_lines = chip_smoke.build_corpus(path, 24_000, seed=0)
    return path, n_lines, chip_smoke.oracle_table(path)


def test_build_corpus_is_seeded_and_sized(tmp_path, corpus):
    path, n_lines, _ = corpus
    again, other = str(tmp_path / "a.txt"), str(tmp_path / "b.txt")
    assert chip_smoke.build_corpus(again, 24_000, seed=0) == n_lines
    chip_smoke.build_corpus(other, 24_000, seed=1)
    data = Path(path).read_bytes()
    assert data == Path(again).read_bytes()
    assert data != Path(other).read_bytes()
    assert 24_000 <= len(data) < 24_000 + 128
    assert data.count(b"\n") == n_lines and 200 < n_lines < 600
    assert max(len(ln) for ln in data.split(b"\n")) <= 128


def test_oracle_table_is_the_shared_counter_oracle(corpus):
    path, _, expect = corpus
    counts = py_wordcount(Path(path).read_bytes().split(b"\n"))
    want = b"".join(
        k + b"\t" + str(v).encode() + b"\n" for k, v in sorted(counts.items())
    )
    assert expect == want and expect.count(b"\n") == len(counts) > 100


def test_phase_wordcount_on_cpu(corpus, capsys):
    path, _, expect = corpus
    chip_smoke.phase_wordcount(path, expect, "cpu", SMALL)
    out = capsys.readouterr().out
    assert "first (compilation included)" in out and "second" in out
    assert "sort_mode=hasht" in out  # config.default_sort_mode("cpu")


def test_phase_stream_on_cpu(corpus, capsys):
    path, _, expect = corpus
    chip_smoke.phase_stream(path, expect, "cpu", SMALL + TPU_MODE)
    assert "table equal to the oracle" in capsys.readouterr().out


def test_phase_mesh_on_four_of_the_eight_virtual_devices(
    corpus, capsys, monkeypatch
):
    from locust_tpu.parallel import mesh

    path, _, expect = corpus
    monkeypatch.setattr(
        mesh, "make_mesh", functools.partial(mesh.make_mesh, 4)
    )
    chip_smoke.phase_mesh(path, expect, "cpu", 4, SMALL + TPU_MODE)
    assert "mesh 4 devices" in capsys.readouterr().out


def test_phase_mesh_refuses_a_wrong_shard_count(corpus):
    """All 8 virtual devices answer, the phase wanted 4: a failure."""
    path, _, expect = corpus
    with pytest.raises(AssertionError, match="wanted 4 non-empty shards"):
        chip_smoke.phase_mesh(path, expect, "cpu", 4, SMALL + TPU_MODE)


GOOD_ERR = "[locust] backend: cpu (device_kind='cpu' count=8)\n"


@pytest.mark.parametrize(
    "got,stderr,match",
    [
        (b"a\t2\n", GOOD_ERR, "differs from the Counter oracle"),
        (b"a\t1\n", GOOD_ERR + "[locust] WARN: table capacity exceeded; "
         "tail keys dropped\n", "lost or demoted"),
        (b"a\t1\n", GOOD_ERR + "WARN: Exceeded emit limit — 3 tokens\n",
         "lost or demoted"),
        (b"a\t1\n", GOOD_ERR + "emit_overflow=0 shuffle_overflow=2\n",
         "lost or demoted"),
        (b"a\t1\n", GOOD_ERR + "sort_mode='fused': kernel not engaged — x\n",
         "lost or demoted"),
        (b"a\t1\n", "[locust] backend: tpu (device_kind='x' count=1)\n",
         "does not name cpu"),
    ],
)
def test_check_cli_fails_the_phase(got, stderr, match):
    with pytest.raises(AssertionError, match=match):
        chip_smoke.check_cli("t", got, stderr, b"a\t1\n", "cpu")


def test_check_cli_passes_a_clean_run():
    chip_smoke.check_cli(
        "t", b"a\t1\n",
        GOOD_ERR + "emit_overflow=0 shuffle_overflow=0 truncated=False\n",
        b"a\t1\n", "cpu",
    )


def test_main_exits_nonzero_without_result_on_a_cpu_backend(capsys):
    assert chip_smoke.main([]) != 0
    cap = capsys.readouterr()
    assert '"ok"' not in cap.out
    assert "'tpu' requested" in cap.err


def _stub_tpu(monkeypatch, kind="TPU v5 lite", count=1):
    from locust_tpu import backend

    monkeypatch.setattr(backend, "select_backend", lambda mode: "tpu")
    monkeypatch.setattr(
        backend, "device_summary",
        lambda: {"platform": "tpu", "kind": kind, "count": count},
    )


@pytest.mark.parametrize(
    "kind,count,argv,match",
    [
        ("TPU v9 imaginary", 1, [], "not in benchmarks/peaks.json"),
        ("TPU v5 lite", 1, ["--chips", "4"], "--chips 4 but jax sees 1"),
        ("TPU v5 lite", 4, [], "--chips 1 but jax sees 4"),
    ],
)
def test_main_refuses_an_unknown_kind_or_a_wrong_count(
    monkeypatch, capsys, kind, count, argv, match
):
    _stub_tpu(monkeypatch, kind, count)
    assert chip_smoke.main(argv) == 2
    cap = capsys.readouterr()
    assert match in cap.err and '"ok"' not in cap.out


def test_result_line_shape_from_a_stubbed_device():
    line = chip_smoke.result_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "extra": "x"}
    )
    assert line == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}'
    )
    assert "\n" not in line and json.loads(line)["ok"] is True


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    """The contract's second refusal: chip_smoke.py and nothing else of
    the repo -> non-zero, no result line (the import of the program fails)."""
    shutil.copy(REPO / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_chip_kernels_is_a_literal_of_known_checks():
    assert isinstance(chip_smoke.CHIP_KERNELS, tuple)
    assert set(chip_smoke.CHIP_KERNELS) <= set(chip_smoke._KERNEL_CHECKS)
    assert "tokenize_block_pallas" in chip_smoke.CHIP_KERNELS
