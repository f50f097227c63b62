"""Step programs: share of the train program's op self-time that no phase
claims — outside ``jvp(`` and ``transpose(`` and under none of ``augment``,
``loss``, ``guards``, ``optimizer`` — with the ops that carry no ``op_name``
at all: the tracing's own health."""

from harness import scopes


def read(run):
    return scopes.train_share_pct(run, "other")
