"""The `ingest-lrc` cell (PR 42): its plain reference, the placement its
set-up gives the two AZs, and a wrong local parity that `correct` must
catch. The program's side — one step of composed rows — is
tests/test_lrc_fold.py's."""

import ast
import os

import numpy as np
import pytest

from cellbench import reference, reference_lrc, run, spec

HERE = os.path.dirname(os.path.abspath(__file__))
TINY = os.path.join(HERE, "traffic", "ingest-lrc.json")


def run_tiny(seconds: float = 1.0, trace: bool = False) -> dict:
    return run.run_cell("ingest-lrc", 2147604123, seconds, trace,
                        device_checks=False, traffic_path=TINY)


def test_the_lrc_reference_is_independent():
    """It imports numpy and the benchmark's own reference and nothing
    of the program under test."""
    tree = ast.parse(open(reference_lrc.__file__).read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add(("." * node.level) + (node.module or "")
                         + ":" + ",".join(a.name for a in node.names))
    assert imported == {"__future__:annotations", "numpy", ".:reference"}
    body = open(reference_lrc.__file__).read().split('"""', 2)[2]
    assert "cubefs_tpu" not in body


@pytest.mark.parametrize("az", [0, 1])
def test_an_azs_local_parity_resolves_its_lost_unit(az):
    """EC16P20L2's local stripe of an AZ is 18 units and one local
    parity: any one of the 18, lost, is solved from the other 17 and the
    parity by `reference`'s own inverse of the local code's rows."""
    n, m, l, azs = 16, 20, 2, 2
    blob = np.random.default_rng([az, 9]).bytes(n * 4096 - 11)
    full = reference_lrc.stripe(blob, n, m, l, azs, 2048)
    units = reference_lrc.az_layout(n, m, l, azs)[az]
    assert len(units) == 19
    gen = reference.encode_matrix(18, 19)  # 19 x 18, systematic
    for lost in (0, 7, 17):  # a data unit and two global parity units
        alive = [j for j in range(19) if j != lost]
        solve = reference._invert(gen[alive])
        members = reference.matmul(solve, full[[units[j] for j in alive]])
        assert np.array_equal(members[lost], full[units[lost]]), lost


def test_the_configuration_states_what_the_port_ships():
    from cellbench.deployment import hold_to_file
    from cubefs_tpu.blob.access import AccessConfig
    from cubefs_tpu.codec import codemode as cm

    config = spec.load_json("cellbench/configs/access-tpu-2az-lrc.json")
    hold_to_file(AccessConfig(engine="tpu"), config["deployment"],
                 config["codemodes"])
    t = cm.tactic(cm.CodeMode.EC16P20L2)
    stated = config["codemodes"]["EC16P20L2"]
    assert (stated["l"], stated["az_count"]) == (t.l, t.az_count)
    assert [cm.Policy(**p) for p in config["policies"]] == [
        cm.Policy("EC16P20L2", 0, 1 << 62)]


def test_the_tiny_run_puts_each_local_stripe_in_one_az_of_its_own():
    result = run_tiny()
    checks = result["detail"]["checks"]
    assert result["correct"] is True, checks
    assert checks["stripes_checked"] >= 1
    for homes in checks["local_stripe_azs"]:
        assert homes == [["az0"], ["az1"]] or homes == [["az1"], ["az0"]]


def test_a_wrong_local_parity_shard_fails_correct(monkeypatch):
    from cellbench.deployment import Deployment

    real = Deployment.unit_call

    def rotten(self, unit, method, bid=None):
        meta, body = real(self, unit, method, bid)
        if method == "get_shard" and unit.index == 37:  # az1's local parity
            body = bytes([body[0] ^ 1]) + body[1:]
        return meta, body

    monkeypatch.setattr(Deployment, "unit_call", rotten)
    result = run_tiny(seconds=0.5)
    assert result["correct"] is False
    assert any("unit 37" in f and "differ" in f
               for f in result["detail"]["checks"]["faults"])
