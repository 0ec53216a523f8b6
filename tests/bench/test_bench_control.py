"""The control (the reference with int16 counts) reads above the limit of
``correct`` where the program reads 0, on inputs large enough that some
count passes 32,767."""
import pytest


@pytest.mark.parametrize("name,tokens", [("wc-wiki-1chip", 1 << 18),
                                         ("hist-ratings-1chip", 1 << 17)])
def test_control_fails_where_the_program_passes(name, tokens, tiny_cell):
    from bench import control
    cell = tiny_cell(name, tokens=tokens, task_size=1024, push_cap=256)
    assert control.control_reading(cell, 2 ** 31 + 1) > 0
    assert control.program_reading(cell, 2 ** 31 + 1) == 0


def test_control_fails_where_the_program_passes_on_four_devices(devices8):
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    out = devices8(f"""
        import sys
        sys.path[:0] = [{root!r}, {os.path.join(root, "tests", "bench")!r}]
        from conftest import make_tiny_cell
        from bench import control
        cell = make_tiny_cell("wc-wiki-4chip-bal", tokens=1 << 18,
                              task_size=1024, push_cap=256)
        print(control.control_reading(cell, 2 ** 31 + 1),
              control.program_reading(cell, 2 ** 31 + 1))
    """, n_devices=4)
    control, program = map(int, out.split()[-2:])
    assert control > 0 and program == 0
