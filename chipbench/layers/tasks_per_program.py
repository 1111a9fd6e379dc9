"""device issue: PTG tasks a device program carries, over the window: the
tasks the ``ptdev`` lane engaged (``PTDEV_STATS["tasks_engaged"]``) over the
programs its manager dispatched (the lane's ``dispatched`` count), as the
graph driver's counters give them. 32 where every k-chain of 32 is one fused
region; 1 with fusion off."""


def read(run):
    tasks = run.counters.get("ptdev.tasks_engaged")
    programs = run.counters.get("ptdev.dispatched")
    return tasks / programs if tasks and programs else None
