"""The engine cell's control, the plain reference computed in bfloat16,
is refused by the comparison with the cell's limits; the program's own
first chunk passes it."""
import jax

import control
from cells import engine_cell


def test_engine_control_is_refused_and_program_passes():
    found = engine_cell()
    r = control.readings(found, 2**31 + 11, program=True,
                         devices=jax.devices())
    assert all(v <= lim for _, v, lim in r["program"]), r["program"]
    assert any(v > lim for _, v, lim in r["control"]), r["control"]
