"""The control's kill is the shipped one."""

import os

import schedule

_REAL = schedule.load_verb(
    os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "faults"),
    "kill_replica")
KILLS = True
run = _REAL.run
