"""perfbench: the repository's performance yardstick (see perfbench/README.md)."""
