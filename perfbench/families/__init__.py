"""One module per benchmark family, found by the ``family`` of a traffic
file: ``perfbench/families/<family>.py`` defines ``Family(config, traffic)``."""
