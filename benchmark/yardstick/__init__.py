"""The yardstick: what decides a number or ``correct``, kept where a PR
that claims a gain cannot change it."""
