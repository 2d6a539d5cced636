"""The lower-precision control fails the row check, while the program
passes it, on three seeds (tiny configurations on the host CPU; the same
readings at the cells' own sizes come from ``bench/control.py`` on the
chip)."""

from bench import control
from bench.tests.helpers import make_root


def test_control_fails_and_program_passes(tmp_path):
    root = make_root(tmp_path, cells=["quad48-lattice"])
    recs = control.readings(root, "quad48-lattice", [11, 2 ** 31 + 7, 99],
                            host=True)
    assert [r["program_mismatched_fields"] for r in recs] == [0, 0, 0]
    assert all(r["control_mismatched_rows"] > 0 for r in recs)
