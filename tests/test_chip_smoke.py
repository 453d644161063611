"""The host-side truth ``chip_smoke.py`` checks the chip against must
itself agree with the repo's reference primitives, and the script must
refuse to run outside a checkout (the driver runs it alone to see it
fail)."""

import os
import random
import shutil
import subprocess
import sys

import pytest

import chip_smoke as cs
from tpuminter import chain
from tpuminter.workloads.hashcore import objective


def test_constants_match_chain():
    assert cs.GENESIS == chain.GENESIS_HEADER.pack()
    assert cs.GENESIS_NONCE == chain.GENESIS_HEADER.nonce
    assert cs.GENESIS_HASH_HEX == chain.GENESIS_HASH_HEX
    assert cs.bits_to_target(cs.DIFF1_BITS) == chain.bits_to_target(
        cs.DIFF1_BITS
    )


@pytest.mark.parametrize("seed", range(4))
def test_covering_bits_is_the_least_compact_target_above(seed):
    rng = random.Random(seed)
    for _ in range(250):
        value = rng.getrandbits(rng.randint(24, 255)) | 1
        bits = cs.covering_bits(value)
        target = chain.bits_to_target(bits)
        assert target >= value and target == cs.bits_to_target(bits)
        mant, exp = bits & 0xFFFFFF, bits >> 24
        assert mant < 0x800000
        if mant > 1:  # one mantissa step down no longer covers it
            assert (mant - 1) << (8 * (exp - 3)) < value


def test_splitmix_truth_matches_objective():
    value, index = cs.splitmix_min(7, 4096)
    assert (value, index) == min((objective(7, i), i) for i in range(4096))


def test_toy_min_truth_matches_chain(tmp_path):
    lib = tmp_path / "libtpuminter_native.so"
    build = subprocess.run(
        ["make", "-C", os.path.join(os.path.dirname(cs.__file__), "native"),
         f"LIB={lib}"], capture_output=True, text=True,
    )
    if build.returncode != 0:
        pytest.skip(f"cannot build the native core: {build.stderr[-500:]}")
    want = min((chain.toy_hash(b"chip-smoke-7", i), i) for i in range(5000))
    assert cs.toy_min_truth("chip-smoke-7", 5000, str(lib)) == want


def test_scrypt_window_matches_chain():
    truth = cs.scrypt_window(8)
    want = sorted(
        (chain.hash_to_int(chain.scrypt_hash(cs.with_nonce(cs.GENESIS, n))), n)
        for n in range(8)
    )
    assert truth == want


def test_refuses_to_run_outside_a_checkout(tmp_path):
    shutil.copy(cs.__file__, tmp_path / "chip_smoke.py")
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path,
        capture_output=True, text=True, timeout=60,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"},
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def _children(pid: int) -> list:
    out = []
    for entry in os.listdir("/proc"):
        try:
            with open(f"/proc/{entry}/stat") as fh:
                stat = fh.read()
        except (OSError, ValueError):
            continue
        if int(stat.rsplit(")", 1)[1].split()[1]) == pid:
            out.append(int(entry))
    return out


def _running(pid: int) -> bool:
    """Alive and not a zombie (a zombie holds no device)."""
    try:
        with open(f"/proc/{pid}/stat") as fh:
            return fh.read().rsplit(")", 1)[1].split()[0] != "Z"
    except FileNotFoundError:
        return False


@pytest.mark.skipif(not os.path.isdir("/proc"), reason="reads /proc")
def test_stop_leaves_no_process_of_the_worker(tmp_path, monkeypatch):
    """The smoke's last check takes the chip in its own process, so the
    worker's device-miner child must have exited by then: ``stop``
    returns only once it has."""
    monkeypatch.setattr(cs, "OUT", str(tmp_path))
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    cl = cs.Cluster(["--backend", "jax"])
    try:
        cl.wait_for("worker", r"device: platform=cpu", 120)
        worker = cl.procs["worker"][0].pid
        children = _children(worker)
    finally:
        cl.stop()
    assert children
    assert not any(_running(pid) for pid in [worker] + children)
