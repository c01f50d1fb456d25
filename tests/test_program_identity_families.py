"""Every program a toy engine of the four families beside the dense and the
expert one warms (``hybrid``, ``latent``, ``mla``, ``swa``: the toys of
tests/vbench_tests, built by the benchmark's own builders), by the sha256 of
its lowered text, as tests/test_program_identity.py holds the other two.
tests/program_digests_families.json was recorded on the parent of PR 43
(0957dbb), whose trunk gained fields (QK-norm a head, an untied head, the
rotary base, the block mask) and hooks (``attend``, ``layer_of``) that these
families share and none of them sets: a change meant to leave their programs
as they are proves it here; one meant to change some shows which in the diff
of the file, rewritten from the repo's root by

    JAX_PLATFORMS=cpu python -m tests.test_program_identity_families
"""

import hashlib
import importlib
import json
import os
import pathlib
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "vbench_tests"))

from vbench import weights  # noqa: E402

DIGESTS = pathlib.Path(__file__).with_name("program_digests_families.json")
FAMILIES = {"hybrid": "test_vbench_hybrid", "latent": "test_vbench_latent",
            "mla": "test_vbench_mla", "swa": "test_vbench_swa"}


class _Recorder:
    """test_program_identity's: a call lowers the jitted attribute at the
    call's arguments, notes the text's digest, then runs it."""

    def __init__(self, name, fn, log):
        self.name, self.fn, self.log = name, fn, log

    def __call__(self, *args, **kwargs):
        text = self.fn.lower(*args, **kwargs).as_text()
        static = [f"{k}={v}" for k, v in sorted(kwargs.items())
                  if isinstance(v, (int, bool))]
        shapes = ["x".join(map(str, a.shape)) for a in args[2:]
                  if hasattr(a, "shape")]
        self.log.setdefault(
            f"{self.name}[{','.join(static)}|{','.join(shapes)}]", []).append(
                hashlib.sha256(text.encode()).hexdigest()[:16])
        return self.fn(*args, **kwargs)

    def __getattr__(self, item):
        return getattr(self.fn, item)


def warmed(family: str) -> dict:
    """{program[static arguments|argument shapes]: digests} of what the
    family's toy engine warms."""
    cfg = importlib.import_module(FAMILIES[family]).TOY
    ref = importlib.import_module(f"vbench.reference.{family}")
    sut = importlib.import_module(f"vbench.sut.{family}")
    w = weights.make_all(7, ref.weight_specs(cfg), cfg["num_hidden_layers"],
                         weights.layer_kinds(ref, cfg))
    eng = sut.build(cfg, w)
    log: dict = {}
    for attr, fn in list(vars(eng).items()):
        if callable(fn) and hasattr(fn, "lower"):
            setattr(eng, attr, _Recorder(attr, fn, log))
    eng._warm_executables()
    return log


@pytest.mark.parametrize("family", list(FAMILIES))
def test_warmed_programs_lower_to_the_recorded_text(family):
    want = json.loads(DIGESTS.read_text())[family]
    got = warmed(family)
    assert sorted(got) == sorted(want)
    moved = [k for k in want if got[k] != want[k]]
    assert not moved, moved


if __name__ == "__main__":
    DIGESTS.write_text(json.dumps(
        {family: warmed(family) for family in FAMILIES},
        indent=1, sort_keys=True) + "\n")
