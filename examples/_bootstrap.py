"""Shared example bootstrap: make the repo importable and honour
QUEST_PLATFORM (e.g. ``QUEST_PLATFORM=cpu``) before jax initialises."""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))

if os.environ.get("QUEST_PLATFORM"):
    import jax

    jax.config.update("jax_platforms", os.environ["QUEST_PLATFORM"])
