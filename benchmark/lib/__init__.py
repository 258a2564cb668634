"""The yardstick: what every cell is measured and judged with. Nothing in
here imports the program under test."""
