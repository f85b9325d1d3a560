"""What no configuration, traffic mix or metric file may need to touch."""

# The one optimizer of every training cell: optax.adamw(LR), moments in the
# weights' dtype. A cell is a model and a traffic mix, not a recipe.
LR = 3e-4
