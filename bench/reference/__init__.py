"""The benchmark's own plain reference: the seeded traffic generators and
the float64 fluid engine, copied so that later changes to the program
cannot move the yardstick. Nothing here imports the program."""
