"""Plain references: seeded operands and the checks that decide ``correct``.

Copies of the program's sound pieces (``ops/potrf.py:spd_tile``,
``chip_smoke.py``'s residual and max-abs checks), kept here so that a later
PR may change the program and not the yardstick.
"""
