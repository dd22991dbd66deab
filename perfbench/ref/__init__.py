"""The plain reference: secp256k1 recovery, Keccak-256 and RLP in
straightforward Python and numpy.  Imports nothing of the program."""
