"""The benchmark's yardstick: everything a run needs that later PRs may
add to but not change. Nothing here imports the program under test."""
